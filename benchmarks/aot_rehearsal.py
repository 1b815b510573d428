"""Compile a cell's real programs for a described v5e, with no chip.

    JAX_PLATFORMS=cpu python benchmarks/aot_rehearsal.py --workload <cell> [<cell> ...]

The third rehearsal of the on-chip-measurement guide. The cell's files are
found by name and its model is built by its family's file, as in a run; the
mix's ``kind`` picks what is compiled: the train step on the job's mesh over
as many chips of a described ``v5e:2x2`` host as the cell asks for, or the
paged prefill (every bucket) and decode programs on one described chip, each
with ``memory_analysis()``. What the chip's compiler would refuse (a kernel it
cannot partition, a program that does not fit) it refuses here, at no chip
time, and the bytes say whether a new cell reaches a quarter of a chip's
memory before it asks for the chip. Nothing runs, so this gives no time and
no result; PERF.md quotes the memory analysis as what decided the depth cut.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding  # noqa: E402

from benchmarks import harness  # noqa: E402


def _analysis(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: getattr(m, k + "_size_in_bytes") for k in ("temp", "argument", "output", "alias")}


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def train(cell: dict, c: dict, job: dict, devices) -> None:
    from ray_tpu.parallel import DEFAULT_RULES, MeshSpec, make_mesh, shardings_from_logical
    from ray_tpu.train.spmd import default_optimizer, make_train_step

    from benchmarks.train_driver import optimizer_shardings

    fam = harness.family(c)
    cfg = fam.model_config(c, job)
    mesh = make_mesh(MeshSpec(**job["mesh"]), devices[: cell["chips"]])
    shardings = shardings_from_logical(fam.param_logical_specs(cfg), DEFAULT_RULES, mesh)
    opt = default_optimizer(**{k: v for k, v in job["optimizer"].items() if k != "name"})
    params = jax.eval_shape(lambda k: fam.init_params(k, cfg), jax.random.key(0))
    params = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), params, shardings
    )
    rep = NamedSharding(mesh, P())
    opt_state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(opt.init, params),
        optimizer_shardings(opt, params, shardings, rep),
    )
    state = {
        "params": params,
        "opt_state": opt_state,
        "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
    }
    bsh = NamedSharding(mesh, P(("dp", "fsdp")))
    tok = jax.ShapeDtypeStruct((job["global_batch"], job["seq_len"]), jnp.int32, sharding=bsh)
    step = make_train_step(
        lambda p, b: fam.loss_fn(p, b, cfg, mesh=mesh), opt,
        mesh=mesh, batch_spec=P(("dp", "fsdp")), param_shardings=shardings,
    )
    t = time.time()
    compiled = step.lower(state, {"tokens": tok, "targets": tok}).compile()
    text = compiled.as_text()
    print(f"{cell['name']}: train step of {c['name']} on {job['mesh']} B={job['global_batch']} "
          f"S={job['seq_len']} remat={job['remat']}: parameters {_nbytes(params)} B in all; "
          f"compiled in {time.time() - t:.0f}s; per device "
          f"{_analysis(compiled)}; tpu_custom_call x{text.count('tpu_custom_call')}; "
          f"all-gather x{text.count('all-gather(') + text.count('all-gather-start(')}, "
          f"reduce-scatter x{text.count('reduce-scatter(')}, all-reduce x{text.count('all-reduce(') + text.count('all-reduce-start(')}",
          flush=True)


def serve(cell: dict, c: dict, mix: dict, devices) -> None:
    from ray_tpu.models import paged

    fam = harness.family(c)
    e = mix["engine"]
    cfg = fam.model_config(c, mix)
    one = SingleDeviceSharding(devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda k: fam.init_params(k, cfg), jax.random.key(0)),
    )
    bs, N, B, W = e["kv_block_size"], e["num_kv_blocks"], e["max_slots"], e["max_seq"] // e["kv_block_size"]
    pool = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: paged.init_block_pool(cfg, N, bs)),
    )
    print(f"{cell['name']}: {c['name']} as served: weights {_nbytes(params)} B, "
          f"KV pool of {N} blocks {_nbytes(pool)} B", flush=True)
    i32 = jnp.int32
    decode = jax.jit(functools.partial(paged.paged_decode, cfg=cfg, block_size=bs))
    t = time.time()
    compiled = decode.lower(params, sds((B,), i32), sds((B,), i32), sds((B, W), i32), pool).compile()
    print(f"paged_decode B={B} W={W}: compiled in {time.time() - t:.0f}s; {_analysis(compiled)}", flush=True)
    prefill = jax.jit(functools.partial(paged.paged_prefill, cfg=cfg, block_size=bs))
    for T in e["prefill_buckets"]:
        t = time.time()
        compiled = prefill.lower(
            params, sds((1, T), i32), sds((), i32), sds((), i32), sds((W,), i32), pool
        ).compile()
        print(f"paged_prefill T={T}: compiled in {time.time() - t:.0f}s; {_analysis(compiled)}", flush=True)


KINDS = {"open-loop": serve, "closed-loop": serve, "train": train}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, nargs="+", help="cells of BENCHMARK.json")
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for name in args.workload:
        cell = harness.cell(name)
        config, traffic = harness.config_of(cell), harness.traffic_of(cell)
        if traffic["kind"] not in KINDS:
            raise SystemExit(f"traffic kind {traffic['kind']!r} has nothing to compile here")
        KINDS[traffic["kind"]](cell, config, traffic, topo.devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
