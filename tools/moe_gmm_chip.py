"""The grouped-product kernel alone, on the chip, against ``jax.lax.ragged_dot``.

    python tools/moe_gmm_chip.py [--rows 128] [--tile-mib 8] [--only nemotron]

For the three expert cells' shapes (the first product ``[K, N]`` and the
down projection ``[N, K]`` of one expert layer), at a decode step's rows and
at a prefill pass of ``ROWS_A_PASS`` rows, with the picks dealt to the held
experts at random as balanced routing deals them: how far
``ops.moe_gmm.gmm`` lies from ``ragged_dot`` on the same operands (largest
absolute difference over the rows that hold a pick; the rows behind them
must read zero), the microseconds a product each takes (``REPEATS`` products
in one program, each fed a mean of the one before it so that none is shared,
timed to ``block_until_ready``), and what share of 819 GB/s the touched
experts' bytes then move at. ``--rows`` and ``--tile-mib`` sweep the kernel's two
constants. Needs a TPU: the kernel does not lower elsewhere, and a time from
another backend says nothing (PERF.md section 6, PR 36, holds the v5e's
readings). The last line of standard output is one JSON list.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.ops import moe_gmm  # noqa: E402

REPEATS = 8
HBM_GB_S = 819.0

# cell: (experts held, K, N of the first product, rows of a decode step, picks
# of it that land here, picks that land here in one prefill pass of 2,048
# rows, experts such a pass reaches: a long prompt's picks on 128 experts
# take three passes, each a stretch of them)
SHAPES = {
    "nemotron": (128, 1024, 2688, 64 * 22, 352, 2048, 43),
    "kimilinear": (64, 2304, 1024, 16 * 8, 32, 2048, 64),
    "axk1": (12, 7168, 2048, 32 * 8, 16, 608, 12),
}


def deal(rng, experts: int, picks: int, reached: int):
    """Group sizes of ``picks`` rows dealt evenly at random over the first
    ``reached`` experts."""
    sizes = np.zeros(experts, np.int32)
    np.add.at(sizes, rng.integers(0, reached, picks), 1)
    return sizes


def chained(product):
    """``REPEATS`` products in one program, through the same weights: each
    takes the rows of the one before it plus a little of its result."""

    @jax.jit
    def run(x, w, sizes):
        def body(x, _):
            y = product(x, w, sizes).astype(jnp.float32)
            return (x + 1e-3 * jnp.mean(y, axis=1, keepdims=True)).astype(x.dtype), None

        return jax.lax.scan(body, x, None, length=REPEATS)[0]

    return run


def us_a_product(run, *operands, iters=5) -> float:
    jax.block_until_ready(run(*operands))  # compiled, outside the timing
    t = time.perf_counter()
    for _ in range(iters):
        out = run(*operands)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / iters / REPEATS * 1e6


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", default=str(moe_gmm._ROWS), help="comma-separated rows a visit")
    ap.add_argument("--tile-mib", default=str(moe_gmm._TILE_BYTES // 2**20),
                    help="comma-separated weight-tile budgets, MiB")
    ap.add_argument("--only", default=",".join(SHAPES), help="comma-separated cells")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU: the kernel's time is a device time")
    rng = np.random.default_rng(0)
    kernel = moe_gmm.gmm.__wrapped__  # re-traced under each setting of the constants
    out = []
    for cell in args.only.split(","):
        E, K0, N0, step_rows, step_picks, pass_picks, pass_reaches = SHAPES[cell]
        for K, N in ((K0, N0), (N0, K0)):
            ks = jax.random.split(jax.random.key(K), 2)
            w = (jax.random.normal(ks[0], (E, K, N), jnp.float32) * K**-0.5).astype(jnp.bfloat16)
            for phase, m, picks, reached in (
                ("decode", step_rows, step_picks, E), ("pass", 2048, pass_picks, pass_reaches),
            ):
                sizes = deal(rng, E, picks, reached)
                touched = int((sizes > 0).sum())
                x = jax.random.normal(ks[1], (m, K), jnp.bfloat16)
                need_us = touched * K * N * 2 / (HBM_GB_S * 1e3)
                sz = jnp.asarray(sizes)
                want = jax.lax.ragged_dot(x, w, sz).astype(jnp.float32)[:picks]
                row = {"cell": cell, "phase": phase, "m": m, "K": K, "N": N, "touched": touched,
                       "need_us": round(need_us, 1)}
                t = us_a_product(chained(jax.lax.ragged_dot), x, w, sz)
                row["ragged_us"], row["ragged_pct"] = round(t, 1), round(100 * need_us / t, 1)
                for rows in map(int, args.rows.split(",")):
                    for mib in map(int, args.tile_mib.split(",")):
                        moe_gmm._ROWS, moe_gmm._TILE_BYTES = rows, mib * 2**20
                        got = jax.jit(lambda *a: kernel(*a))(x, w, sz).astype(jnp.float32)  # traced anew
                        err = float(jnp.max(jnp.abs(got[:picks] - want)))
                        behind = float(jnp.max(jnp.abs(got[picks:]))) if picks < m else 0.0
                        t = us_a_product(chained(kernel), x, w, sz)
                        tag = f"gmm_r{rows}_t{mib}"
                        row[tag + "_us"], row[tag + "_pct"] = round(t, 1), round(100 * need_us / t, 1)
                        row[tag + "_err"], row[tag + "_behind"] = round(err, 4), behind
                print(json.dumps(row), flush=True)
                out.append(row)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
