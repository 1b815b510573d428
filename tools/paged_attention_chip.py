"""The decode attention kernel alone, on the chip, against the gather.

    python tools/paged_attention_chip.py [--chunks 128,256,512]

Sixteen slots over a pool of Mistral-7B's shapes (16 layers, 2,049 blocks of
16, 8 KV heads of 128, tables of 2,048 positions): for each mix of live
lengths, how far ``ops.paged_attention.paged_decode_attention`` lies from
``paged._attend_gathered`` on the same operands (the largest absolute
difference of the outputs summed over the layers, a layer), and the
microseconds a layer each takes (a scan over the sixteen layers, timed to
``block_until_ready``). ``--chunks`` sweeps the
kernel's chunk length. Needs a TPU: the kernel does not lower elsewhere, and
a time from another backend says nothing (PERF.md section 6, PR 32, holds
the v5e's readings). The last line of standard output is one JSON list.
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

from ray_tpu.models import paged  # noqa: E402
from ray_tpu.ops import paged_attention  # noqa: E402

B, KH, G, DH, BLOCK, W, L, N = 16, 8, 4, 128, 16, 128, 16, 2049


def per_layer(attend):
    """``attend`` over every layer of the pool in one program."""

    @jax.jit
    def run(q, pk, pv, tables, lengths):
        def body(acc, layer):
            out = attend(q, pk, pv, layer, tables, lengths)
            return acc + out.astype(jnp.float32), None

        layers = jnp.arange(L, dtype=jnp.int32)
        return jax.lax.scan(body, jnp.zeros(q.shape, jnp.float32), layers)[0]

    return run


def us_a_layer(run, *operands, iters=20) -> float:
    jax.block_until_ready(run(*operands))  # compiled, outside the timing
    t = time.perf_counter()
    for _ in range(iters):
        out = run(*operands)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / iters / L * 1e6


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chunks", default=str(paged_attention._CHUNK),
                    help="comma-separated chunk lengths to sweep")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU: the kernel's time is a device time")
    ks = jax.random.split(jax.random.key(0), 3)
    pk = jax.random.normal(ks[0], (L, N, KH, BLOCK, DH), jnp.bfloat16)
    pv = jax.random.normal(ks[1], (L, N, KH, BLOCK, DH), jnp.bfloat16)
    q = jax.random.normal(ks[2], (B, KH, G, DH), jnp.bfloat16)
    rng = np.random.default_rng(0)
    tables = jnp.asarray(
        np.stack([rng.permutation(np.arange(1, N))[:W] for _ in range(B)]),
        jnp.int32,
    )
    mixes = {
        "batch-backlog": rng.integers(128, 769, B),  # the mix's live lengths
        "sixteen of 400": np.full(B, 400),
        "free slots": np.ones(B),  # one block each, on the scratch block
        "block edges": np.array([16, 17, 32, 1, 128, 129, 127, 2048, 255, 256,
                                 257, 15, 31, 33, 1000, 2047]),
        "tables full": np.full(B, W * BLOCK),
    }
    gather = per_layer(paged._attend_gathered)
    rows = []
    for chunk in map(int, args.chunks.split(",")):
        paged_attention._CHUNK = chunk
        jax.clear_caches()
        kernel = per_layer(paged_attention.paged_decode_attention)
        for name, lens in mixes.items():
            operands = (q, pk, pv, tables, jnp.asarray(lens, jnp.int32))
            diff = jnp.max(jnp.abs(kernel(*operands) - gather(*operands))) / L
            rows.append({
                "chunk": chunk, "mix": name, "live_positions": int(lens.sum()),
                "max_abs_diff": round(float(diff), 5),
                "kernel_us_a_layer": round(us_a_layer(kernel, *operands), 2),
                "gather_us_a_layer": round(us_a_layer(gather, *operands), 2),
            })
            print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
