"""Perf sweep for the GPT-2 train step on the local chip.

Measures ms/step and tokens/s/chip for combinations of batch size, remat
policy, and flash-attention block sizes, plus standalone kernel timings.
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
from ray_tpu.ops.attention import causal_attention
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


def _time_chained(fn, carry, *args, iters_a=8, iters_b=40):
    """Time fn(carry, *args) -> carry with a serial data dependency.

    Every iteration consumes the previous output, and timing runs at two
    iteration counts and reports the slope, which cancels whatever constant
    cost the dispatch and the final wait add.
    """
    c = carry
    for _ in range(3):
        c = fn(c, *args)
    jax.block_until_ready(c)

    def run(n):
        nonlocal c
        t0 = time.perf_counter()
        for _ in range(n):
            c = fn(c, *args)
        jax.block_until_ready(c)
        return time.perf_counter() - t0

    t_a = run(iters_a)
    t_b = run(iters_b)
    return (t_b - t_a) / (iters_b - iters_a)


def sweep_attention():
    print("== flash attention kernel sweep (B=16, H=12, S=1024, D=64) ==")
    B, H, S, D = 16, 12, 1024, 64
    ks = jax.random.split(jax.random.key(0), 4)
    q, k, v = (
        jax.random.normal(kk, (B, H, S, D), jnp.bfloat16) for kk in ks[:3]
    )

    def fwd_chain(impl, bq, bk):
        # Chain the output back into q: a serial dependency.
        return jax.jit(
            lambda q, k, v: causal_attention(
                q, k, v, impl=impl, block_q=bq, block_k=bk
            )
        )

    def bwd_chain(impl, bq, bk):
        def f(q, k, v):
            return jnp.sum(
                causal_attention(
                    q, k, v, impl=impl, block_q=bq, block_k=bk
                ).astype(jnp.float32)
                ** 2
            )

        g = jax.grad(f, argnums=(0, 1, 2))
        # dq chains into q (tanh keeps values bounded across iterations).
        return jax.jit(lambda q, k, v: jnp.tanh(g(q, k, v)[0]))

    for bq in (256, 512, 1024):
        for bk in (256, 512, 1024):
            t_f = _time_chained(fwd_chain("pallas", bq, bk), q, k, v) * 1e3
            t_b = _time_chained(bwd_chain("pallas", bq, bk), q, k, v) * 1e3
            print(f"  bq={bq:4d} bk={bk:4d}: fwd {t_f:6.2f} ms  fwd+bwd {t_b:6.2f} ms")
    t_f = _time_chained(fwd_chain("reference", 256, 256), q, k, v) * 1e3
    t_b = _time_chained(bwd_chain("reference", 256, 256), q, k, v) * 1e3
    print(f"  reference (jnp): fwd {t_f:6.2f} ms  fwd+bwd {t_b:6.2f} ms")


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
