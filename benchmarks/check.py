"""Are the program's outputs right? The program against the plain reference,
over as many seeds as asked, and the controls that must come out wrong.

    python benchmarks/check.py --config mistral-7b-v0.3 --seeds 1,2,3 \
        --who program,fp8,displaced
    python benchmarks/check.py --config gpt2-xl --traffic pretrain-s1024 \
        --seeds 1,2,3 --who program,fp8

``--who``: ``program`` is the system under test (the paged prefill and decode
programs for a Llama-family configuration; loss, gradients and logits of the
train step's loss function under the job's mesh for GPT-2). ``fp8`` and
``bf16`` put the reference itself, computed in that precision, in the
program's place: the control. ``displaced`` is the program with its block
tables shifted by one entry before decoding: a cache fault the comparison has
to catch. Every run of a cell makes the ``program`` comparison once, on its
own seed, against the limits in ``benchmarks/limits/<config>.json``; those
limits were set from this script's readings (PERF.md section 2).

Runs in one process on whatever chips JAX finds; without a TPU it fails
unless ``--cpu-rehearsal`` (tiny sizes) is named. The last line of standard
output is one JSON object.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import harness, model_build  # noqa: E402

DECODE_STEPS = 3
CHECK_PROMPTS = (200, 77)  # two buckets, two slots, lengths off any boundary
CHECK_SEQUENCES = 4  # train: one per fsdp shard


def check_llama(c: dict, engine: dict, seed: int, who: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import llama_ref
    from benchmarks.reference.common import rel_err
    from ray_tpu.models import paged

    bs, S = engine["kv_block_size"], engine["max_seq"]
    B, N, W = engine["max_slots"], engine["num_kv_blocks"], S // bs
    K = DECODE_STEPS
    lens = [min(n, max(engine["prefill_buckets"]) - K - 1) for n in CHECK_PROMPTS]
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, c["vocab_size"], size=(len(lens), max(lens) + K)).astype(np.int32)
    weights = llama_ref.init_weights(seed, c)
    ref = jax.jit(functools.partial(llama_ref.forward, c=c, quant=None))

    def compared(logits):  # the last prompt position and the K after it
        return jnp.concatenate([logits[i, n - 1 : n + K] for i, n in enumerate(lens)])

    want = compared(ref(weights, jnp.asarray(tokens)))
    if who in ("fp8", "bf16"):
        ctl = jax.jit(functools.partial(llama_ref.forward, c=c, quant=who))
        return {"logits_rel_err": rel_err(compared(ctl(weights, jnp.asarray(tokens))), want)}

    cfg = model_build.llama_config(c, S)
    prefill = jax.jit(functools.partial(paged.paged_prefill, cfg=cfg, block_size=bs))
    decode = jax.jit(functools.partial(paged.paged_decode, cfg=cfg, block_size=bs))
    pool = paged.init_block_pool(cfg, N, bs)
    free = list(rng.permutation(np.arange(1, N)))  # scattered, as after churn
    slots = rng.choice(B, size=len(lens), replace=False)
    tables = np.zeros((B, W), np.int32)
    rows = []
    for i, n in enumerate(lens):
        need = -(-(n + K) // bs)
        tables[slots[i], :need] = [free.pop() for _ in range(need)]
        bucket = min(b for b in engine["prefill_buckets"] if b >= n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = tokens[i, :n]
        pool, logits = prefill(
            weights, jnp.asarray(toks), jnp.asarray(n, jnp.int32),
            jnp.asarray(0, jnp.int32), jnp.asarray(tables[slots[i]]), pool,
        )
        rows.append([logits])
    if who == "displaced":
        tables = np.roll(tables, 1, axis=1)
    elif who != "program":
        raise SystemExit(f"unknown --who {who!r}")
    for k in range(K):
        last = np.zeros(B, np.int32)
        pos = np.zeros(B, np.int32)
        for i, n in enumerate(lens):
            last[slots[i]], pos[slots[i]] = tokens[i, n + k], n + k
        pool, logits = decode(
            weights, jnp.asarray(last), jnp.asarray(pos), jnp.asarray(tables), pool
        )
        for i in range(len(lens)):
            rows[i].append(logits[slots[i]])
    got = jnp.stack([x for row in rows for x in row])
    return {"logits_rel_err": rel_err(got, want)}


def check_gpt2(c: dict, job: dict, seed: int, who: str, devices=None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.reference import gpt2_ref
    from benchmarks.reference.common import rel_err
    from ray_tpu.models import gpt2
    from ray_tpu.parallel import DEFAULT_RULES, MeshSpec, make_mesh, shardings_from_logical

    cfg = model_build.gpt2_config(c, job)
    mesh = make_mesh(MeshSpec(**job["mesh"]), devices or jax.devices())
    shardings = shardings_from_logical(gpt2.param_logical_specs(cfg), DEFAULT_RULES, mesh)
    weights = jax.device_put(gpt2_ref.init_weights(seed, c), shardings)
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, size=(CHECK_SEQUENCES, job["seq_len"])).astype(np.int32)
    batch = jax.device_put(
        {"tokens": toks, "targets": np.roll(toks, -1, axis=1)},
        NamedSharding(mesh, P(("dp", "fsdp"))),
    )
    ref_grads = jax.jit(functools.partial(gpt2_ref.loss_and_grads, c=c, quant=None))
    ref_logits = jax.jit(functools.partial(gpt2_ref.forward, c=c, quant=None))
    want_loss, want_grads = ref_grads(weights, batch)
    if who in ("fp8", "bf16"):
        got_loss, got_grads = jax.jit(
            functools.partial(gpt2_ref.loss_and_grads, c=c, quant=who)
        )(weights, batch)
        got_logits = jax.jit(functools.partial(gpt2_ref.forward, c=c, quant=who))(
            weights, batch["tokens"]
        )
    elif who == "program":
        (got_loss, _m), got_grads = jax.jit(
            jax.value_and_grad(
                lambda p, b: gpt2.loss_fn(p, b, cfg, mesh=mesh), has_aux=True
            )
        )(weights, batch)
        got_logits = jax.jit(lambda p, t: gpt2.forward(p, t, cfg, mesh=mesh))(
            weights, batch["tokens"]
        )
    else:
        raise SystemExit(f"unknown --who {who!r}")
    out = {"grad_rel_err": rel_err(got_grads, want_grads)}
    del got_grads, want_grads
    out["logits_rel_err"] = rel_err(got_logits, ref_logits(weights, batch["tokens"]))
    out["loss"] = [float(got_loss), float(want_loss)]
    return out


def check_one(config: dict, traffic: dict, seed: int, who: str) -> dict:
    """By the configuration's family; ``traffic`` gives the engine settings
    of a serve mix or the mesh and sequence length of a training job."""
    if config["family"] == "llama":
        return check_llama(config, traffic["engine"], seed, who)
    if config["family"] == "gpt2":
        return check_gpt2(config, traffic, seed, who)
    raise SystemExit(f"no check for family {config['family']!r}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", help="a mix or job under benchmarks/traffic/; "
                    "default: that of the first cell of this configuration")
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--who", default="program")
    ap.add_argument("--out", help="also write the result here")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()

    from ray_tpu.util.compile_cache import CacheCounter, ensure_compile_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ensure_compile_cache()
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
        ).strip()
    import jax

    cells = [w for w in harness.benchmark()["workloads"] if w["config"] == args.config]
    if not cells:
        raise SystemExit(f"no cell of BENCHMARK.json runs {args.config!r}")
    config = harness.config_of(cells[0])
    traffic = harness.load_json(harness.HERE, "traffic", (args.traffic or cells[0]["traffic"]) + ".json")
    if args.cpu_rehearsal:
        config, traffic = harness.shrink_for_rehearsal(config, traffic)
    devices = jax.devices()
    if devices[0].platform != ("cpu" if args.cpu_rehearsal else "tpu"):
        raise SystemExit(f"JAX finds {devices[0].platform}, not a TPU")
    cache = CacheCounter()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        for who in args.who.split(","):
            row = {"seed": seed, "who": who, **check_one(config, traffic, seed, who)}
            print(json.dumps(row), file=sys.stderr, flush=True)
            rows.append(row)
            jax.clear_caches()  # drop this row's executables and what they hold
            gc.collect()
    stats = [d.memory_stats() or {} for d in devices]
    result = {
        "device": {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0) for s in stats),
        },
        "config": args.config,
        "rows": rows,
        "compile_cache": {"hits": cache.hits, "misses": cache.misses},
    }
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
