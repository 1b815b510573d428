"""A prefill's keys and values written into the pool alone, on the chip: a row
of a head at a time against a block at a time.

    python tools/pool_write_chip.py [--shapes mistral512,mimo_window,...]

``paged._write`` (what decode, verify and the handoff use: one update a
(position, KV head), a row of ``Dh`` lanes) against ``paged._write_blocks``
(what every prefill program uses since PR 52: one update a block, ``KH x block
x Dh`` contiguous) at the shapes of the cells whose prefills write keys and
values per head: Mistral's 512- and 1,024-token buckets (8 heads of 128), a
2,048-token chunk of MiMo's window part (8 heads, keys in rows of 256 lanes)
and of its full part (4 heads), Trinity's and Solar's (8 heads of 128),
Granite's packed rows (4 heads, ``[value | key]`` in 128 lanes, a 768 bucket).
Every layer of a pool tensor is written once a call, each after the one
before as in a model, the pool donated, under a shuffled block table from a
``start`` behind one earlier chunk. A line a setting: the microseconds a
write by the device trace and by the host's clock, the share of 819 GB/s that
is over the bytes written once, whether the two forms leave the same pool
(``array_equal``), and the trace's largest operations. Needs a TPU: a time
from another backend says nothing (PERF.md section 6, PR 52, holds the v5e's
readings). The last line of standard output is one JSON list.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import trace_reduce  # noqa: E402
from ray_tpu.models import paged  # noqa: E402

CALLS = 8
BLOCK = 16
HBM_BYTES_A_US = 819e3  # a v5e's, as benchmarks/peaks.json has it
# layers of the pool tensor, blocks, KV heads, lanes of a row, rows a prefill, table width
SHAPES = {
    "mistral512": (16, 4096, 8, 128, 512, 128),
    "mistral1024": (16, 4096, 8, 128, 1024, 128),
    "mimo_window_k": (5, 2048, 8, 256, 2048, 1152),
    "mimo_window_v": (5, 2048, 8, 128, 2048, 1152),
    "mimo_full_k": (2, 8192, 4, 256, 2048, 1152),
    "trinity": (5, 4096, 8, 128, 2048, 1024),
    "granite": (4, 4096, 4, 128, 768, 128),
}


def program(form, layers, rows):
    """Every layer written once: ``(pool, table, start, new) -> pool``."""

    def run(pool, table, start, new):
        pos = start + jnp.arange(rows, dtype=jnp.int32)
        for l in range(layers):
            if form == "rows":
                pool = paged._write(pool, l, table[pos // BLOCK], pos % BLOCK, new)
            else:
                pool = paged._write_blocks(pool, l, table, start, new, BLOCK)
            # As in a model, a layer's rows wait for the layer before.
            new = new + (0.0 * pool[l, 0, 0, 0, 0]).astype(new.dtype)
        return pool

    return jax.jit(run, donate_argnums=0)


def time_calls(run, pool, *operands):
    """Host and device microseconds a call over ``CALLS`` calls, the pool
    handed on from call to call as the engine hands its own on."""
    pool = run(pool, *operands)  # compiled, outside the timing
    jax.block_until_ready(pool)
    log_dir = tempfile.mkdtemp(prefix="pool_write_chip_")
    try:
        jax.profiler.start_trace(log_dir)
        t = time.perf_counter()
        for _ in range(CALLS):
            pool = run(pool, *operands)
        jax.block_until_ready(pool)
        host_us = (time.perf_counter() - t) / CALLS * 1e6
        jax.profiler.stop_trace()
        reduced = trace_reduce.reduce(
            trace_reduce.plain_from_xplane(trace_reduce.find_xplane(log_dir))
        )
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    ops = [[name, round(s / CALLS * 1e6, 1)] for name, s in reduced["ops"][:4]]
    return host_us, reduced["busy_s"] / CALLS * 1e6, ops


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES), help="comma-separated, of " + ", ".join(SHAPES))
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU: a write's time is a device time")
    out = []
    for name in args.shapes.split(","):
        layers, blocks, KH, lanes, rows, W = SHAPES[name]
        rng = np.random.default_rng(layers * rows)
        table = jnp.asarray(rng.permutation(np.arange(1, blocks))[:W], jnp.int32)
        start = jnp.asarray(rows, jnp.int32)  # behind one earlier chunk
        new = jax.random.normal(jax.random.key(rows), (rows, KH, lanes), jnp.bfloat16)
        fresh = lambda: jnp.zeros((layers, blocks, KH, BLOCK, lanes), jnp.bfloat16)  # noqa: E731
        want = None
        for form in ("rows", "blocks"):
            run = program(form, layers, rows)
            got = run(fresh(), table, start, new)
            same = True if want is None else bool(jnp.array_equal(got, want))
            want = got if want is None else want
            host_us, device_us, ops = time_calls(run, fresh(), table, start, new)
            written = rows * KH * lanes * 2
            row = {
                "shape": name, "form": form, "layers": layers,
                "updates_a_write": rows * KH if form == "rows" else rows // BLOCK,
                "device_us_a_write": round(device_us / layers, 1),
                "host_us_a_write": round(host_us / layers, 1),
                "pct_of_bytes_speed": round(100 * written / HBM_BYTES_A_US / (device_us / layers), 1),
                "same_pool": same, "ops_us_a_call": ops,
            }
            print(json.dumps(row), file=sys.stderr, flush=True)
            out.append(row)
        del want, got
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
