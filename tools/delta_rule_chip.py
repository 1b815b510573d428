"""The delta rule's chunked prefill alone, on the chip: its two arms side by
side, the plain arm by the rows of a block or against another copy of the
module.

    python tools/delta_rule_chip.py [--heads 64,32] [--tokens 2048,512] [--groups 4,8,16]
    python tools/delta_rule_chip.py --blocks 8,16,32,64
    python tools/delta_rule_chip.py --against _parent/ray_tpu/ops/delta_rule.py

The scan at the shapes of the two cells that run it
(`serve-longdoc-solaropen2`: 64 heads a layer; `serve-batch-kimilinear`: 32;
keys and values 128 wide, a chunk of 2,048 tokens and Kimi Linear's smallest
served bucket, 512), a line an arm: ``plain``, ``ops.delta_rule.kda_chunked``
(the ``lax.scan``), and ``kernel``, ``ops.delta_scan.kda_scan`` (one Pallas
call, the state and a chunk's values held on the chip) at its own heads a grid
step or at each of ``--groups``, with ``kernel_ms`` the call alone beside
``device_ms``, which also holds the fusions that lay the tool's ``[T, H, d]``
operands flat (in a model's program the projection's fusion leaves them so),
and ``first_call_s`` the seconds to trace, lower and compile it. The plain arm is traced anew
under each setting of :data:`ops.delta_rule.BLOCK`. A block of
:data:`CHUNK` rows forms every pair term elementwise over ``[H, C, C, d_k]``,
as before PR 44, beside a product with no column left to take (10.6 ms at 64
heads where that PR's parent read 9.17). A line a setting: the milliseconds a call
takes by the host's clock (to ``block_until_ready``) and by the device trace,
how far its result lies from the first setting's, and the trace's operations
a call, longest first (milliseconds). With ``--against <path to a
delta_rule.py>`` (another commit's, from ``git archive``; more than once for
more than one), this checkout's module and each of those are timed beside one
another at their own ``BLOCK`` (or at each of ``--blocks``, where given): the
same line a module, ``module`` naming it, its differences those from this
checkout's result. Needs a TPU: a time from another backend says nothing
(PERF.md section 6, PR 44 and PR 45, holds the v5e's readings). The last line
of standard output is one JSON list.
"""

from __future__ import annotations

import argparse
import functools
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks import trace_reduce  # noqa: E402
from ray_tpu.ops import delta_rule, delta_scan  # noqa: E402

CALLS = 8
WIDTH = 128


def inputs(key, T, H, d=WIDTH):
    """As `models/kda.py:_kda_inputs` hands them over: unit keys, queries
    scaled, log decays from the initialiser's range, ``beta`` in (0, 2)."""
    ks = jax.random.split(key, 6)
    l2 = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    return (
        l2(jax.random.normal(ks[0], (T, H, d))) * d**-0.5,
        l2(jax.random.normal(ks[1], (T, H, d))),
        jax.random.normal(ks[2], (T, H, d)),
        -jax.random.uniform(ks[3], (T, H, d), minval=0.001, maxval=1.6),
        2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (T, H))),
        jax.random.normal(ks[5], (H, d, d)),
    )


def time_calls(run, args, top):
    """Host and device milliseconds a call over ``CALLS`` calls, and the
    device's operations a call."""
    jax.block_until_ready(run(*args))  # compiled, outside the timing
    log_dir = tempfile.mkdtemp(prefix="delta_rule_chip_")
    try:
        jax.profiler.start_trace(log_dir)
        t = time.perf_counter()
        for _ in range(CALLS):
            out = run(*args)
        jax.block_until_ready(out)
        host_ms = (time.perf_counter() - t) / CALLS * 1e3
        jax.profiler.stop_trace()
        reduced = trace_reduce.reduce(
            trace_reduce.plain_from_xplane(trace_reduce.find_xplane(log_dir))
        )
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    ops = [[name, round(s / CALLS * 1e3, 3)] for name, s in reduced["ops"][:top]]
    return host_ms, reduced["busy_s"] / CALLS * 1e3, ops


def load(path):
    """Another copy of ``ops/delta_rule.py``, as a module of its own."""
    spec = importlib.util.spec_from_file_location("delta_rule_at_" + str(abs(hash(path))), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--blocks", default=None,
        help=f"comma-separated rows a block (8,16,32,{delta_rule.CHUNK}; with --against each module's own)",
    )
    ap.add_argument(
        "--against", action="append", default=[], metavar="PATH",
        help="another delta_rule.py to time beside this checkout's; may be given again",
    )
    ap.add_argument("--heads", default="64,32", help="comma-separated heads a layer")
    ap.add_argument("--tokens", default="2048,512", help="comma-separated tokens a call")
    ap.add_argument(
        "--groups", default=None,
        help="comma-separated heads a grid step for the kernel arm (default: the module's own)",
    )
    ap.add_argument("--ops", type=int, default=12, help="operations printed a line, longest first")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU: the scan's time is a device time")
    modules = [("this checkout", delta_rule)] + [(path, load(path)) for path in args.against]
    if args.blocks:
        blocks = lambda module: map(int, args.blocks.split(","))  # noqa: E731
    elif args.against:
        blocks = lambda module: [module.BLOCK]  # noqa: E731
    else:
        blocks = lambda module: [8, 16, 32, delta_rule.CHUNK]  # noqa: E731
    out = []
    groups = list(map(int, args.groups.split(","))) if args.groups else [None]
    for H, T in ((H, T) for H in map(int, args.heads.split(",")) for T in map(int, args.tokens.split(","))):
        operands = inputs(jax.random.key(H), T, H)
        first = None

        def line(run, **named):
            nonlocal first
            t = time.perf_counter()
            o, S = jax.block_until_ready(run(*operands))
            first_call_s = time.perf_counter() - t
            first = first or (o, S)
            host_ms, device_ms, ops = time_calls(run, operands, args.ops)
            row = {
                **named, "heads": H, "tokens": T, "first_call_s": round(first_call_s, 2),
                "host_ms": round(host_ms, 3), "device_ms": round(device_ms, 3),
                "kernel_ms": sum(ms for name, ms in ops if name.startswith("kda_scan")) or None,
                "o_diff": float(jnp.max(jnp.abs(o - first[0]))),
                "S_diff": float(jnp.max(jnp.abs(S - first[1]))),
                "ops_ms": ops,
            }
            print(json.dumps(row), flush=True)
            out.append(row)

        for (name, module), block in ((m, b) for m in modules for b in blocks(m[1])):
            module.BLOCK = block
            jax.clear_caches()  # the scan keeps its body's trace by the function
            line(jax.jit(module.kda_chunked), arm="plain", module=name, block=block)
        for group in (g for g in groups if g is None or H % g == 0):
            jax.clear_caches()
            line(
                jax.jit(functools.partial(delta_scan.kda_scan, group=group)),
                arm="kernel", module="this checkout", group=group or delta_scan.head_group(H),
            )
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
