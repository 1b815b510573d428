"""The decode attention kernel alone, on the chip, against the gather.

    python tools/paged_attention_chip.py [--latent | --kind full|window] [--chunks 128,256,512]
    python tools/paged_attention_chip.py --prefill [--tile-rows 2048,4096] [--score-elements 1048576]

Sixteen slots over a pool of Mistral-7B's shapes (16 layers, 2,049 blocks of
16, 8 KV heads of 128, tables of 2,048 positions): for each mix of live
lengths, how far ``ops.paged_attention.paged_decode_attention`` lies from
``paged._attend_gathered`` on the same operands (the largest absolute
difference of the outputs summed over the layers, a layer), and the
microseconds a layer each takes (a scan over the sixteen layers, timed to
``block_until_ready``). ``--latent`` does the same for the latent arm at
A.X-K1's shapes (32 slots over a pool of 7 layers, 8,193 blocks of 16 rows of
640, tables of 4,096 positions, 64 heads, values 512 wide):
``paged_latent_decode_attention`` against ``paged._attend_latent_gathered``,
the first mix's lengths drawn from ``reasoning-backlog``'s tables, and each
row also says what share of the live blocks' bytes' speed (819 GB/s) the
kernel reached. ``--kind full`` and ``--kind window`` do it for the two
kinds of attention layer of MiMo-V2.5's cell (32 slots, tables of 18,432
positions, keys of 192 in rows of 256 lanes beside values of 128): 4
key/value heads of 16 queries over 6k-17k live rows in a two-layer part; 8
key/value heads of 8 queries (padded to 16) with a window of 128 and a sink
over a five-layer part, where the bytes are those of the blocks the walk
covers. (A key buffer of 192 lanes cannot be timed beside the rows of 256:
Mosaic refuses a copy that is not whole lane tiles, PERF.md section 6, PR
48.) ``--chunks`` sweeps the kernel's chunk length. ``--prefill`` times the
prefill kernel (``ops.paged_prefill_attention``) against the fold it stands in
for (``paged._prefill_fold``) at the shapes of the three cells that prefill
in chunks: a chunk of 2,048 at starts 0, 4k, 8k and 14k under each kind of
layer of MiMo-V2.5, Solar-Open2 and Trinity, microseconds a layer, the
largest difference of the outputs, and the share of the array's peak (197
TFLOP/s) by the pairs the mathematics needs at the widths it needs (a key of
192, not the 256 lanes of its row); ``--tile-rows`` and ``--score-elements`` sweep
the rows a tile of queries may hold and the scores of a (tile, stretch).
``--head64`` times the three layouts in which heads of 64 can be attended in
place (PR 51), at Granite-4.0-H-Micro's cell (64 slots of 100-1,150 live rows
drawn from ``chat-backlog``, 8 key/value heads of 4 queries, a four-layer
part of 8,193 blocks) and at one long shape (8 slots of 16,384 rows): (a)
``packed``, a head's value and key side by side in one pool row of 128 lanes
(``paged_packed_decode_attention``); (b) ``pairs``, two heads a lane tile in
each of two pools, the pair's queries laid against their own half; (c)
``padded``, keys and values each padded to 128 lanes, twice the cache; each
against the gather over the packed pool, with the share of the bytes' speed by
the bytes the mathematics needs (2,048 B a position and layer). Needs a TPU:
the kernel does not lower elsewhere, and a time from another backend says
nothing (PERF.md section 6, PR 32 and PR 39, holds the v5e's readings). The last line of standard output is one JSON list.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.models import paged  # noqa: E402
from ray_tpu.ops import paged_attention, paged_prefill_attention  # noqa: E402

B, KH, G, DH, BLOCK, W, L, N = 16, 8, 4, 128, 16, 128, 16, 2049
# The latent arm's: slots, heads, row and value widths, table, layers, blocks.
LB, LH, LC, LR, LW, LL, LN = 32, 64, 640, 512, 256, 7, 8193
HBM_BYTES_A_US = 819e3  # a v5e's, as benchmarks/peaks.json has it


def per_layer(attend, layers):
    """``attend`` over every layer of the pools in one program."""

    @jax.jit
    def run(q, pools, tables, lengths):
        out = jax.eval_shape(attend, q, *pools, jnp.int32(0), tables, lengths)

        def body(acc, layer):
            return acc + attend(q, *pools, layer, tables, lengths).astype(jnp.float32), None

        return jax.lax.scan(
            body, jnp.zeros(out.shape, jnp.float32), jnp.arange(layers, dtype=jnp.int32)
        )[0]

    return run


def us_a_layer(run, layers, *operands, iters=20) -> float:
    jax.block_until_ready(run(*operands))  # compiled, outside the timing
    t = time.perf_counter()
    for _ in range(iters):
        out = run(*operands)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / iters / layers * 1e6


def tables_of(rng, slots, width, blocks):
    return jnp.asarray(
        np.stack([rng.permutation(np.arange(1, blocks))[:width] for _ in range(slots)]),
        jnp.int32,
    )


def per_head(rng):
    """``(operands, mixes, gather, kernel, the name of its chunk, layers,
    bytes a live block)`` of the arm for keys and values per head."""
    ks = jax.random.split(jax.random.key(0), 3)
    pk = jax.random.normal(ks[0], (L, N, KH, BLOCK, DH), jnp.bfloat16)
    pv = jax.random.normal(ks[1], (L, N, KH, BLOCK, DH), jnp.bfloat16)
    q = jax.random.normal(ks[2], (B, KH, G, DH), jnp.bfloat16)
    mixes = {
        "batch-backlog": rng.integers(128, 769, B),  # the mix's live lengths
        "sixteen of 400": np.full(B, 400),
        "free slots": np.ones(B),  # one block each, on the scratch block
        "block edges": np.array([16, 17, 32, 1, 128, 129, 127, 2048, 255, 256,
                                 257, 15, 31, 33, 1000, 2047]),
        "tables full": np.full(B, W * BLOCK),
    }
    return (
        (q, (pk, pv), tables_of(rng, B, W, N)), mixes, paged._attend_gathered,
        paged_attention.paged_decode_attention, "_CHUNK", L, 2 * KH * BLOCK * DH * 2,
    )


def latent(rng):
    """The same of the latent arm. A slot of ``reasoning-backlog`` holds its
    prompt and as much of its answer as it has got to."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "traffic", "reasoning-backlog.json")) as f:
        mix = json.load(f)
    ks = jax.random.split(jax.random.key(0), 2)
    # rows of 640 with zeros behind the 576 of the latent row, as the engine writes them
    pool = jax.random.normal(ks[0], (LL, LN, BLOCK, LC), jnp.bfloat16).at[..., 576:].set(0)
    ql = jax.random.normal(ks[1], (LB, LH, LC), jnp.bfloat16) * 0.3
    drawn = rng.choice(mix["prompt_tokens"], LB) + (
        rng.random(LB) * rng.choice(mix["output_tokens"], LB)
    ).astype(int)
    mixes = {
        "reasoning-backlog": drawn,
        "thirty-two of 1930": np.full(LB, 1930),
        "free slots": np.ones(LB),
        "block and chunk edges": np.array(
            [16, 17, 32, 1, 128, 129, 127, 2048, 255, 256, 257, 15, 31, 33, 1000, 2047,
             511, 512, 513, 1023, 1024, 1025, 4095, 4096, 3000, 3001, 2, 100, 500, 1500,
             2500, 3500]),
        "tables full": np.full(LB, LW * BLOCK),
    }
    static = dict(value_width=LR, scale=0.1147)
    return (
        (ql, (pool,), tables_of(rng, LB, LW, LN)), mixes,
        functools.partial(paged._attend_latent_gathered, **static),
        functools.partial(paged_attention.paged_latent_decode_attention, **static),
        "_LATENT_CHUNK", LL, BLOCK * LC * 2,
    )


# MiMo-V2.5's cell: slots, table width, key and value widths, the key pool's lanes.
MB, MW, MDK, MDV, MLANES, MWINDOW = 32, 1152, 192, 128, 256, 128
KINDS = {  # layers of the part, its blocks, key/value heads, queries a head, window, sink
    "full": (2, 36865, 4, 16, None, False),
    "window": (5, 32 * 137 + 1, 8, 8, MWINDOW, True),
}


def per_kind(kind: str, rng):
    """The same of one kind of MiMo-V2.5's attention layers; and the blocks a
    slot's walk covers, for the bytes."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "traffic", "longdoc-backlog.json")) as f:
        mix = json.load(f)
    layers, blocks, kh, g, window, has_sink = KINDS[kind]
    ks = jax.random.split(jax.random.key(0), 4)
    pk = jax.random.normal(ks[0], (layers, blocks, kh, BLOCK, MLANES), jnp.bfloat16).at[..., MDK:].set(0)
    pv = jax.random.normal(ks[1], (layers, blocks, kh, BLOCK, MDV), jnp.bfloat16)
    q = jax.random.normal(ks[2], (MB, kh, g, MDK), jnp.bfloat16)
    sink = (5.0 + 0.5 * jax.random.normal(ks[3], (kh, g)),) if has_sink else ()
    drawn = rng.choice(mix["prompt_tokens"], MB) + (
        rng.random(MB) * rng.choice(mix["output_tokens"], MB)
    ).astype(int)
    mixes = {
        "longdoc-backlog": drawn,
        "thirty-two of 10880": np.full(MB, 10880),
        "free slots": np.ones(MB),
        "block and window edges": np.array(
            [16, 17, 32, 1, 128, 129, 127, 2048, 255, 256, 257, 15, 31, 33, 1000, 2047,
             143, 144, 145, 1023, 1024, 1025, 4095, 4096, 3000, 3001, 2, 100, 500, 16384,
             17000, 18432]),
        "tables full": np.full(MB, MW * BLOCK),
    }
    static = {} if window is None else {"window": window}

    def covered(lens):
        first = 0 if window is None else np.maximum(lens - window, 0) // BLOCK
        return -(-lens // BLOCK) - first

    gather = lambda q, pk, pv, l, t, n: paged._attend_gathered(q, pk, pv, l, t, n, *sink, **static)  # noqa: E731
    kernel = lambda q, pk, pv, l, t, n: paged_attention.paged_decode_attention(  # noqa: E731
        q, pk, pv, l, t, n, *sink, **static)
    return (
        (q, (pk, pv), tables_of(rng, MB, MW, blocks)), mixes, gather, kernel, "_CHUNK", layers,
        kh * BLOCK * (MLANES + MDV) * 2, covered,
    )


def head64(rng) -> list:
    """Heads of 64 in place, by layout (module docstring)."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "traffic", "chat-backlog.json")) as f:
        mix = json.load(f)
    kh, g, dh, layers, blocks, scale = 8, 4, 64, 4, 8193, 1 / 64
    ks = jax.random.split(jax.random.key(0), 3)
    k = jax.random.normal(ks[0], (layers, blocks, kh, BLOCK, dh), jnp.bfloat16)
    v = jax.random.normal(ks[1], (layers, blocks, kh, BLOCK, dh), jnp.bfloat16)
    zeros = jnp.zeros_like(k)
    pools = {
        "packed": (jnp.concatenate([v, k], axis=-1),),
        "pairs": tuple(
            x.reshape(layers, blocks, kh // 2, 2, BLOCK, dh).transpose(0, 1, 2, 4, 3, 5)
            .reshape(layers, blocks, kh // 2, BLOCK, 2 * dh) for x in (k, v)),
        "padded": (jnp.concatenate([k, zeros], axis=-1), jnp.concatenate([v, zeros], axis=-1)),
    }
    del k, v, zeros

    def pairs(q, pk, pv, l, t, n):
        b = q.shape[0]
        q2 = q.reshape(b, kh // 2, 2, g, dh)
        z = jnp.zeros_like(q2[:, :, 0])
        laid = jnp.concatenate([  # the pair's first head against lanes 0-63, its second against 64-127
            jnp.concatenate([q2[:, :, 0], z], axis=-1), jnp.concatenate([z, q2[:, :, 1]], axis=-1)], axis=2)
        out = paged_attention.paged_decode_attention(laid, pk, pv, l, t, n, scale=scale)  # [b, kh/2, 2g, 2dh]
        return jnp.stack([out[:, :, :g, :dh], out[:, :, g:, dh:]], axis=2).reshape(b, kh, g, dh)

    kernels = {
        "packed": functools.partial(paged_attention.paged_packed_decode_attention, scale=scale),
        "pairs": pairs,
        "padded": lambda q, pk, pv, l, t, n: paged_attention.paged_decode_attention(
            q, pk, pv, l, t, n, scale=scale)[..., :dh],
    }
    gather = per_layer(functools.partial(paged._attend_packed_gathered, scale=scale), layers)
    drawn = rng.choice(mix["prompt_tokens"], 64) + (
        rng.random(64) * rng.choice(mix["output_tokens"], 64)).astype(int)
    shapes = {"chat-backlog (64 slots)": (drawn, 128), "long (8 slots of 16384)": (np.full(8, 16384), 1024)}
    rows = []
    for name, (lens, width) in shapes.items():
        q = jax.random.normal(ks[2], (len(lens), kh, g, dh), jnp.bfloat16) * 4
        tables = jnp.asarray(
            rng.permutation(np.arange(1, blocks))[: len(lens) * width].reshape(len(lens), width), jnp.int32)
        lengths = jnp.asarray(lens, jnp.int32)
        want = gather(q, pools["packed"], tables, lengths)
        needed = int((-(-lens // BLOCK)).sum()) * kh * BLOCK * 2 * dh * 2  # the live blocks, a layer
        gather_us = us_a_layer(gather, layers, q, pools["packed"], tables, lengths)
        for layout, kernel in kernels.items():
            run = per_layer(kernel, layers)
            operands = (q, pools[layout], tables, lengths)
            diff = jnp.max(jnp.abs(run(*operands) - want)) / layers
            us = us_a_layer(run, layers, *operands)
            rows.append({
                "shape": name, "layout": layout, "live_positions": int(lens.sum()),
                "pool_bytes_a_position_and_layer": sum(p.shape[-1] * p.shape[2] * 2 for p in pools[layout]),
                "max_abs_diff": round(float(diff), 5), "kernel_us_a_layer": round(us, 2),
                "gather_us_a_layer": round(gather_us, 2),
                "kernel_pct_of_bytes_speed": round(100 * needed / HBM_BYTES_A_US / us, 1),
            })
            print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    return rows


# The chunked cells' kinds of layer: layers of the part, key/value heads,
# queries a head, a key's width and its row's, a value's, window, sink, table.
CHUNK, PEAK_FLOPS_A_US = 2048, 197e6
PREFILL_KINDS = {
    "mimov25 full": (2, 4, 16, MDK, MLANES, MDV, None, False, MW),
    "mimov25 window": (5, 8, 8, MDK, MLANES, MDV, MWINDOW, True, MW),
    "solaropen2": (1, 8, 8, 128, 128, 128, None, False, 1152),
    "trinity full": (1, 8, 6, 128, 128, 128, None, False, 1024),
    "trinity window": (4, 8, 6, 128, 128, 128, 4096, False, 1024),
}
STARTS = (0, 4096, 8192, 14336)


def prefill(args, rng) -> list:
    """A chunk's attention a layer, the kernel against the fold, by kind of
    layer and by where in its document the chunk starts."""
    ppa = paged_prefill_attention
    rows, fold_us = [], {}  # the fold is timed once: no variant of the kernel's changes it
    for tile_rows in map(int, args.tile_rows.split(",")):
        for elements in map(int, args.score_elements.split(",")):
            ppa._TILE_ROWS, ppa._SCORE_ELEMENTS = tile_rows, elements
            jax.clear_caches()
            for name, (layers, kh, g, dk, lanes, dv, window, has_sink, width) in PREFILL_KINDS.items():
                blocks = width + 1
                ks = jax.random.split(jax.random.key(0), 4)
                pk = jax.random.normal(ks[0], (layers, blocks, kh, BLOCK, lanes), jnp.bfloat16)
                pk = pk.at[..., dk:].set(0)
                pv = jax.random.normal(ks[1], (layers, blocks, kh, BLOCK, dv), jnp.bfloat16)
                q = jax.random.normal(ks[2], (CHUNK, kh, g, dk), jnp.bfloat16)
                sink = 5.0 + 0.5 * jax.random.normal(ks[3], (kh, g)) if has_sink else None
                table = jnp.asarray(rng.permutation(np.arange(1, blocks))[:width], jnp.int32)

                def kernel(q, pk, pv, l, table, start):
                    return ppa.paged_prefill_attention(
                        q, pk, pv, l, table, start, start + CHUNK, sink, window=window)

                def fold(q, pk, pv, l, table, start):
                    return paged._prefill_fold(
                        q, pk, pv, l, table, start + jnp.arange(CHUNK, dtype=jnp.int32),
                        start + CHUNK, block_size=BLOCK, window=window, sink=sink)

                runs = {}
                for arm, attend in (("kernel", kernel), ("fold", fold)):
                    @jax.jit
                    def run(q, pk, pv, table, start, attend=attend):
                        def body(acc, layer):
                            return acc + attend(q, pk, pv, layer, table, start).astype(jnp.float32), None
                        return jax.lax.scan(
                            body, jnp.zeros((CHUNK, kh, g, dv), jnp.float32),
                            jnp.arange(layers, dtype=jnp.int32))[0]
                    runs[arm] = run
                for start in STARTS:
                    if start + CHUNK > width * BLOCK:
                        continue
                    operands = (q, pk, pv, table, jnp.int32(start))
                    diff = jnp.max(jnp.abs(runs["kernel"](*operands) - runs["fold"](*operands))) / layers
                    at = start + np.arange(CHUNK) + 1  # the keys a query sees
                    pairs = int((at if window is None else np.minimum(at, window)).sum())
                    flops = 2 * pairs * kh * g * (dk + dv)
                    if (name, start) not in fold_us:
                        fold_us[name, start] = us_a_layer(runs["fold"], layers, *operands, iters=10)
                    us = {"kernel": us_a_layer(runs["kernel"], layers, *operands, iters=10),
                          "fold": fold_us[name, start]}
                    rows.append({
                        "tile_rows": tile_rows, "score_elements": elements,
                        "tile": (tile := ppa.tile(CHUNK, g, window)),
                        "stretch": ppa.stretch(tile, g, BLOCK, window), "kind": name, "start": start,
                        "max_abs_diff": round(float(diff), 5),
                        "kernel_us_a_layer": round(us["kernel"], 1),
                        "fold_us_a_layer": round(us["fold"], 1),
                        "kernel_pct_of_peak": round(100 * flops / PEAK_FLOPS_A_US / us["kernel"], 1),
                        "fold_pct_of_peak": round(100 * flops / PEAK_FLOPS_A_US / us["fold"], 1),
                    })
                    print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--prefill", action="store_true",
                    help="the prefill kernel against the fold at the three chunked cells' shapes")
    ap.add_argument("--tile-rows", default=str(paged_prefill_attention._TILE_ROWS),
                    help="with --prefill: comma-separated bounds on a tile's rows to sweep")
    ap.add_argument("--score-elements", default=str(paged_prefill_attention._SCORE_ELEMENTS),
                    help="with --prefill: comma-separated sizes of a (tile, stretch)'s scores to sweep")
    ap.add_argument("--head64", action="store_true",
                    help="the layouts that attend heads of 64 in place, at Granite-4.0-H-Micro's shapes")
    ap.add_argument("--latent", action="store_true",
                    help="the latent arm at A.X-K1's shapes")
    ap.add_argument("--kind", choices=sorted(KINDS), help="a kind of MiMo-V2.5's attention layers")
    ap.add_argument("--chunks", default=None,
                    help="comma-separated chunk lengths to sweep (default: the arm's own)")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU: the kernel's time is a device time")
    rng = np.random.default_rng(0)
    if args.prefill or args.head64:
        print(json.dumps(prefill(args, rng) if args.prefill else head64(rng)))
        return 0
    arm = functools.partial(per_kind, args.kind) if args.kind else latent if args.latent else per_head
    (q, pools, tables), mixes, attend, kernel_of, chunk_name, layers, block_bytes, *covered = arm(rng)
    covered = covered[0] if covered else lambda lens: -(-lens // BLOCK)  # a slot's live blocks
    chunks = args.chunks or str(getattr(paged_attention, chunk_name))
    gather = per_layer(attend, layers)
    rows = []
    for chunk in map(int, chunks.split(",")):
        setattr(paged_attention, chunk_name, chunk)
        jax.clear_caches()
        kernel = per_layer(kernel_of, layers)
        for name, lens in mixes.items():
            operands = (q, pools, tables, jnp.asarray(lens, jnp.int32))
            diff = jnp.max(jnp.abs(kernel(*operands) - gather(*operands))) / layers
            kernel_us = us_a_layer(kernel, layers, *operands)
            live_bytes = int(covered(lens).sum()) * block_bytes
            rows.append({
                "chunk": chunk, "mix": name, "live_positions": int(lens.sum()),
                "max_abs_diff": round(float(diff), 5),
                "kernel_us_a_layer": round(kernel_us, 2),
                "gather_us_a_layer": round(us_a_layer(gather, layers, *operands), 2),
                "kernel_pct_of_bytes_speed": round(100 * live_bytes / HBM_BYTES_A_US / kernel_us, 1),
            })
            print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
