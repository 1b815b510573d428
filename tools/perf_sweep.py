"""Perf sweep for the GPT-2 train step on the local chip.

Measures ms/step and tokens/s/chip for combinations of batch size and remat
policy, and the two flash-attention kernels alone (device microseconds a
call from the profiler's trace, by block sizes and by the rows of a diagonal
tile's groups, at the shard shape of `train-gpt2xl-fsdp4` and four others).
Usage:
    python tools/perf_sweep.py            # full sweep
    python tools/perf_sweep.py step       # train-step sweep only
    python tools/perf_sweep.py attn       # attention-kernel sweep only
"""

from __future__ import annotations

import os
import sys
import time

# Runs as a script from anywhere; the repo root is one level up.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.models import gpt2
from ray_tpu.parallel import (
    DEFAULT_RULES,
    MeshSpec,
    make_mesh,
    shardings_from_logical,
)
from ray_tpu.train.spmd import (
    default_optimizer,
    make_train_state,
    make_train_step,
)


# (B, H, S, D): the shard of `train-gpt2xl-fsdp4` (fsdp=4: 2 of 8 sequences,
# 25 heads), GPT-2-125M at 16 a chip, two longer sequences, a head of 128
# (Llama).
ATTN_SHAPES = (
    (2, 25, 1024, 64), (16, 12, 1024, 64), (2, 12, 2048, 64), (2, 12, 4096, 64),
    (2, 16, 1024, 128),
)
ATTN_BLOCKS = (256, 512, 1024)
ATTN_GROUPS = (None, 512, 256, 128)  # None: a straddling tile scored whole
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9  # a v5e's, as benchmarks/peaks.json has it
ATTN_CALLS = 16


def attn_least_us(B, H, S, D):
    """The least a v5e could take for the forward and for the backward
    kernel, as benchmarks/layer_metrics/flash_roofline_pct.py reckons it."""
    fwd_ops = B * H * 2 * 2 * D * S * (S + 1) / 2
    tensor, row = B * H * S * D * 2, B * H * S * 4
    return tuple(
        1e6 * max(ops / PEAK_FLOPS, nbytes / PEAK_BYTES)
        for ops, nbytes in (
            (fwd_ops, 4 * tensor + row), (2 * fwd_ops, 7 * tensor + 2 * row)
        )
    )


def kernel_us(step, args, log_dir):
    """Mean device microseconds of one flash_fwd and one flash_bwd call over
    ATTN_CALLS runs of ``step``, from the profiler's trace (what
    flash_roofline_pct reads), and the host's clock over the same runs."""
    from benchmarks import trace_reduce

    jax.block_until_ready(step(*args))
    jax.profiler.start_trace(log_dir)
    t0 = time.perf_counter()
    q = args[0]
    for _ in range(ATTN_CALLS):
        q = step(q, *args[1:])
    jax.block_until_ready(q)
    host_us = (time.perf_counter() - t0) / ATTN_CALLS * 1e6
    jax.profiler.stop_trace()
    trace = trace_reduce.plain_from_xplane(trace_reduce.find_xplane(log_dir))
    took = {"flash_fwd": [], "flash_bwd": []}
    for plane in trace["planes"]:
        if plane["name"] != "/device:TPU:0":
            continue
        for line in plane["lines"]:
            if line["name"] != trace_reduce.OPS_LINE:
                continue
            for name, _, dur_ns in line["events"]:
                for kernel in took:
                    if name.startswith(kernel):
                        took[kernel].append(dur_ns / 1e3)
    if not (took["flash_fwd"] and took["flash_bwd"]):
        raise SystemExit("no flash_fwd / flash_bwd operation on /device:TPU:0: needs a TPU")
    return (
        sum(took["flash_fwd"]) / len(took["flash_fwd"]),
        sum(took["flash_bwd"]) / len(took["flash_bwd"]),
        host_us,
    )


def sweep_attention():
    """The two flash kernels alone: microseconds a call by the device trace,
    the share of the roofline time, and score pairs computed over needed,
    by block sizes and by the rows of a diagonal tile's groups."""
    import shutil
    import tempfile

    from ray_tpu.ops import attention

    for B, H, S, D in ATTN_SHAPES:
        least_f, least_b = attn_least_us(B, H, S, D)
        print(
            f"== flash kernels B={B} H={H} S={S} D={D}: least fwd {least_f:.1f} us, "
            f"bwd {least_b:.1f} us (* the group the kernels choose) =="
        )
        ks = jax.random.split(jax.random.key(0), 3)
        q, k, v = (jax.random.normal(kk, (B, H, S, D), jnp.bfloat16) for kk in ks)
        for bq in ATTN_BLOCKS:
            for bk in ATTN_BLOCKS:
                for group in ATTN_GROUPS:
                    if group is not None and (bq != bk or bq % group or bq == group):
                        continue

                    def loss(q, k, v):
                        o = attention._flash_attention(
                            q, k, v, D**-0.5, bq, bk, group, False, None
                        )
                        return jnp.sum(o.astype(jnp.float32) ** 2)

                    # dq chains into q (tanh keeps values bounded).
                    step = jax.jit(
                        lambda q, k, v: jnp.tanh(jax.grad(loss, argnums=(0, 1, 2))(q, k, v)[0])
                    )
                    log_dir = tempfile.mkdtemp(prefix="attn_sweep_")
                    try:
                        f_us, b_us, host_us = kernel_us(step, (q, k, v), log_dir)
                    except Exception as e:  # a shape the compiler refuses: report, go on
                        print(f"  bq={bq:4d} bk={bk:4d} group={group}: FAIL {str(e)[:200]!r}")
                        continue
                    finally:
                        shutil.rmtree(log_dir, ignore_errors=True)
                    computed, needed = attention.causal_pairs(S, bq, bk, group)
                    chosen = "*" if group == attention.diag_group(bq, bk) else " "
                    print(
                        f" {chosen}bq={bq:4d} bk={bk:4d} group={str(group):>4s}: fwd {f_us:7.1f} us "
                        f"({100 * least_f / f_us:4.1f}%)  bwd {b_us:7.1f} us "
                        f"({100 * least_b / b_us:4.1f}%)  fwd+bwd {f_us + b_us:7.1f} us "
                        f"({100 * (least_f + least_b) / (f_us + b_us):4.1f}% of roofline)  "
                        f"host {host_us:7.1f} us a step  pairs {computed / needed:.3f}",
                        flush=True,
                    )


def sweep_step():
    devices = jax.devices()
    n_dev = len(devices)
    mesh = make_mesh(MeshSpec(dp=n_dev), devices)
    opt = default_optimizer(total_steps=1000)
    seq = 1024

    print(f"== train-step sweep ({n_dev} x {devices[0].device_kind}) ==")
    for remat in ("mlp", "dots", "full", "none"):
        for per_chip_batch in (8, 16, 24, 32):
            cfg = gpt2.GPT2Config(remat=remat)
            B = per_chip_batch * n_dev
            try:
                shardings = shardings_from_logical(
                    gpt2.param_logical_specs(cfg), DEFAULT_RULES, mesh
                )
                state = make_train_state(
                    lambda k: gpt2.init_params(k, cfg),
                    opt,
                    jax.random.key(0),
                    param_shardings=shardings,
                )
                step = make_train_step(
                    lambda p, b: gpt2.loss_fn(p, b, cfg, mesh=mesh),
                    opt,
                    mesh=mesh,
                    batch_spec=P(("dp", "fsdp")),
                    param_shardings=shardings,
                )
                tokens = jax.random.randint(
                    jax.random.key(1), (B, seq), 0, cfg.vocab_size
                )
                batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}

                # State chains through the loop (donated buffers); the
                # two-point slope cancels constant dispatch cost.
                for _ in range(2):
                    state, metrics = step(state, batch)
                jax.block_until_ready(metrics["loss"])

                def run(n, state):
                    t0 = time.perf_counter()
                    for _ in range(n):
                        state, metrics = step(state, batch)
                    jax.block_until_ready(metrics["loss"])
                    return time.perf_counter() - t0, state

                t_a, state = run(3, state)
                t_b, state = run(13, state)
                dt = (t_b - t_a) / 10
                tps = B * seq / dt / n_dev
                print(
                    f"  remat={remat:5s} B/chip={per_chip_batch:2d}: "
                    f"{dt * 1e3:7.1f} ms/step  {tps:9,.0f} tok/s/chip"
                )
            except Exception as e:
                msg = f"{type(e).__name__}"
                oom = any(
                    s in f"{e}" for s in ("RESOURCE_EXHAUSTED", "Out of memory", "OOM", "hbm")
                )
                print(
                    f"  remat={remat:5s} B/chip={per_chip_batch:2d}: "
                    f"{'OOM' if oom else 'FAIL ' + msg}"
                )
                if not oom:
                    raise


if __name__ == "__main__":
    what = sys.argv[1] if len(sys.argv) > 1 else "all"
    if what in ("all", "attn"):
        sweep_attention()
    if what in ("all", "step"):
        sweep_step()
