"""Prefill attention over a block pool, read where it lies.

A chunk of ``T`` consecutive query positions attends the keys and values its
sequence has in the pool by now, its own among them: the decode walk of
``ops/paged_attention.py`` with a tile of queries where that has one
position. One Pallas call a layer over a grid of (KV head, tile of query
positions). The ``group`` query heads of a KV head are rows of one operand
(``group x tile`` rows against one copy of a key stretch: 16 x 256 at 16
heads a group, 8 x 512 and 6 x 512 at 8 and 6: :func:`tile`), so each key
block is copied once a tile for all its query heads. The blocks a tile sees
come from the pool in HBM into VMEM through the table, a stretch of blocks at
a time (:func:`stretch`), double-buffered (the next tile's first stretch in
flight behind this tile's last), and each stretch is folded into a running
(max, denominator, accumulator) softmax in float32: ``q k^T`` is formed once
a (tile, stretch).

**A walk bounded from what the tile can see.** The last stretch is the one
that holds the tile's last position (or ``n_keys - 1``, the last row the
pool holds); with ``window``, the first is the block that holds ``first
position - window + 1``, and the table's entries before it are never read
(the engine has given those blocks back). Only the stretches on a bound
build a mask: those that reach past the tile's first position (``column <=
position``) and those that begin before the last position's window
(``position - column < window``). A window of 128 under tiles of 128 reads
sixteen blocks, one stretch, not the 1,024 columns of two stretches of 512.

Operands and numerics are the decode kernel's: the pools ``[L, N, KH, block,
lanes]`` handed over whole in ``memory_space=ANY`` with the layer, the
chunk's first position and ``n_keys`` as prefetched scalars; a key pool's
rows may be wider than a key (zeros up to whole lane tiles) beside values of
another width; matmuls in the pool's dtype with float32 accumulation, base-2
exponentials, and a learned *sink* a query head starts a row's fold from
``(sink, 1, 0)``.

A module of its own: a Mosaic call's source lines are part of its
compile-cache key, so lines added above the decode kernels would recompile
every decode program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _LOG2E, _NEG_INF

# Rows of a tile: the query heads of a KV head times a tile's positions. What
# a stretch costs beside its products (two copies a live block to start, a
# tenth of the kernel's time at 4,096 rows) is paid once for all of a tile's
# rows, so a tile takes as many positions as keep it within these rows,
# between ``_MIN_TILE`` and ``_MAX_TILE``: on a v5e, 8 query heads of 128
# lanes behind 14k keys read 36% of the array's peak at 1,024 rows a tile, 46
# at 2,048 and 56 at 4,096 (PERF.md section 6, PR 50).
_TILE_ROWS = 4096
_MIN_TILE, _MAX_TILE = 128, 512
# The float32 scores of one (tile, stretch), in elements: the stretch is as
# many key positions as keep ``rows x stretch`` within it (8 MiB), between
# ``_MIN_STRETCH`` and ``_MAX_STRETCH``. What a row costs a stretch whatever
# the keys (its maximum and its sum across lanes, its accumulator scaled
# again) is a fifth to a quarter of a stretch of 512: at 256 keys a tile of
# 4,096 rows reads 45% of the array's peak, at 512 52% (PERF.md section 6).
_SCORE_ELEMENTS = 2 * 1024 * 1024
_MIN_STRETCH, _MAX_STRETCH = 128, 512
# The shortest prefill that takes the kernel (:func:`fits` says why).
_MIN_TOKENS = 2048
# What a call may take of a core's VMEM (128 MiB on a v5e; the compiler's
# default scope is 16): the scores, their exponentials in both dtypes and a
# mask are each ``_SCORE_ELEMENTS`` wide, beside two tiles of queries and of
# outputs, the accumulator, and the row statistics, whose [rows, 1] float32
# a lane tile pads to [rows, 128].
_VMEM_LIMIT_BYTES = 64 * 2**20


def _lane_tiles(n: int) -> int:
    return -(-n // 128) * 128


def tile(tokens: int, group: int, window: int | None = None) -> int:
    """Query positions a tile: the most of 512, 256 and 128 that divide the
    chunk and keep ``group x tile`` within ``_TILE_ROWS``, and no more than a
    window in whole lane tiles (a tile longer than its window scores mostly
    what its rows do not see)."""
    most = min(_MAX_TILE, max(_MIN_TILE, _TILE_ROWS // group))
    if window is not None:
        most = min(most, max(_MIN_TILE, _lane_tiles(window)))
    n = _MAX_TILE
    while n > _MIN_TILE and (n > most or tokens % n):
        n //= 2
    return n


def stretch(tile: int, group: int, block_size: int, window: int | None = None) -> int:
    """Key positions a fold, in whole blocks and whole lane tiles: what keeps
    the scores within ``_SCORE_ELEMENTS``, and for a window no more than a
    tile's whole walk (the window and the tile: a window of 128 under tiles
    of 128 is one stretch of 256, all of it in flight before its tile)."""
    n = _MAX_STRETCH
    while n > _MIN_STRETCH and group * tile * n > _SCORE_ELEMENTS:
        n //= 2
    if window is not None:
        n = min(n, _lane_tiles(window + tile))
    return max(1, n // block_size) * block_size


def fits(
    tokens: int, group: int, key_lanes: int, value_dim: int, block_size: int,
    window: int | None = None,
) -> bool:
    """Whether the kernel takes a kind of layer at these shapes. Its copies
    and matmuls are whole TPU tiles: the chunk in whole tiles of queries, the
    lane width in a key's stored width and in a value's, the bf16 sublane
    tile in the block, and whole blocks to a stretch. And a window is no
    longer than a tile of the kind's queries: a tile's walk under a longer
    one is the window and the tile, nine stretches of 512 under 4,096, on a
    bound at both ends, and what a tile costs whatever its walk (its
    statistics carried through a loop more) outweighs what the kernel saves
    there: on a v5e 2,572 us a layer against the fold's 2,448 at 6 query
    heads a window of 4,096, where the same heads with no window take 4,614
    against 4,756 (PERF.md section 6, PR 50). And the prefill is a chunk's
    length, ``_MIN_TOKENS``: a shorter one is a short prompt's whole prefill,
    where attention is under a millisecond of the program, and every program
    that holds the kernel traces and lowers it again on every start to find
    itself in the compile cache, 0.75 s a prefill bucket of the benchmark
    host's set-up (Trinity's mixed queue warms three buckets under 2,048)."""
    return (
        tokens >= _MIN_TOKENS
        and tokens % _MIN_TILE == 0
        and key_lanes % 128 == 0
        and value_dim % 128 == 0
        and block_size % 16 == 0
        and _MIN_STRETCH % block_size == 0
        and (window is None or window <= tile(tokens, group, window))
    )


def _kernel(
    scalars_ref, table_ref,  # scalar prefetch (SMEM): [layer, start, n_keys]; [W]
    *refs,  # (the sinks,) q, the two pools, o, a stretch buffer a pool, sems, parity
    block, pages, window=None, sink=False,
):
    """``refs``: with ``sink``, first the sinks [KH * G] float32 SMEM; ``q``
    [1, G, tile, lanes] VMEM; the pools ``[L, N, KH, block, D]`` left in HBM,
    keys then values; ``o`` [1, G, tile, Dv] VMEM; for each pool its stretch
    buffer [2, pages * block, D] VMEM; DMA semaphores [2 (pool), 2 (buffer)];
    SMEM [1]: the buffer the tile's first stretch was copied to."""
    if sink:
        sink_ref, *refs = refs
    q_ref, pk, pv, o_ref, kbuf, vbuf, sems, parity = refs
    h, t = pl.program_id(0), pl.program_id(1)
    heads, tiles = pl.num_programs(0), pl.num_programs(1)
    layer, start, n_keys = scalars_ref[0], scalars_ref[1], scalars_ref[2]
    _, G, tile, lanes = q_ref.shape
    Dv = o_ref.shape[-1]
    rows, span = G * tile, pages * block

    def walk(tile_index):
        """``(the table entry a tile's walk starts at, the blocks it
        covers)``: to the block of the last position the tile sees, from the
        block of the first one its window keeps."""
        p0 = start + tile_index * tile
        last = jnp.minimum(p0 + tile - 1, n_keys - 1) // block
        if window is None:
            return 0, last + 1
        first = jnp.minimum(jnp.maximum(p0 - window + 1, 0) // block, last)
        return first, last + 1 - first

    def copies(head, tile_index, i, buf, j):
        """The copies of live block ``j`` of stretch ``i`` of a tile's walk,
        one a pool: one head's rows of a block are contiguous in a pool."""
        page = table_ref[walk(tile_index)[0] + i * pages + j]
        dst = pl.ds(pl.multiple_of(j * block, block), block)
        return [
            pltpu.make_async_copy(hbm.at[layer, page, head], vmem.at[buf, dst, :], sems.at[p, buf])
            for p, (hbm, vmem) in enumerate(((pk, kbuf), (pv, vbuf)))
        ]

    def live(tile_index, i):
        """The live blocks of stretch ``i`` of a tile's walk: ``pages`` of
        every stretch but a walk's last."""
        return jnp.minimum(pages, walk(tile_index)[1] - i * pages)

    def for_each_copy(head, tile_index, i, buf, act):
        """``act`` on the copies of a stretch, two a live block."""

        def body(j, _):
            for c in copies(head, tile_index, i, buf, j):
                act(c)
            return _

        jax.lax.fori_loop(0, live(tile_index, i), body, None)

    def begin(head, tile_index, i, buf):
        for_each_copy(head, tile_index, i, buf, lambda c: c.start())

    def finish(head, tile_index, i, buf):
        n = live(tile_index, i)

        @pl.when(n == pages)
        def _all():
            # Every copy of a pool signals that pool's semaphore by its
            # bytes: one wait for a whole buffer's takes them all.
            for p, vmem in enumerate((kbuf, vbuf)):
                pltpu.make_async_copy(vmem.at[buf], vmem.at[buf], sems.at[p, buf]).wait()

        @pl.when(n < pages)
        def _some():
            for_each_copy(head, tile_index, i, buf, lambda c: c.wait())

    @pl.when((h == 0) & (t == 0))
    def _first():
        # Rows past a walk's last block keep what an earlier stretch left
        # there; before any stretch that is whatever VMEM held, and a masked
        # probability of zero times a NaN is a NaN. (A padding row sees such
        # columns of keys unmasked: its numbers mean nothing, and are finite.)
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        parity[0] = 0
        begin(0, 0, 0, 0)

    begun = parity[0]
    first, blocks = walk(t)
    n = (blocks + pages - 1) // pages
    p0 = start + t * tile
    q = q_ref[0].reshape(rows, lanes)  # scaled by the caller: scores in base 2
    # Rows are (query head, position of the tile): a row's position.
    pos = p0 + jax.lax.broadcasted_iota(jnp.int32, (G, tile, 1), 1).reshape(rows, 1)

    def fold(i, carry, masked):
        m, l, acc = carry
        buf = (begun + i) % 2
        # The stretch after this one, of this tile or the first of the next
        # tile's (the next head's first tile behind a head's last), goes
        # into the other buffer while this one is attended.
        ends = i + 1 == n
        wraps = ends & (t + 1 == tiles)
        nh = jnp.where(wraps, h + 1, h)
        nt = jnp.where(ends, jnp.where(wraps, 0, t + 1), t)

        @pl.when(nh < heads)
        def _prefetch():
            begin(nh, nt, jnp.where(ends, 0, i + 1), 1 - buf)

        finish(h, t, i, buf)
        k, v = kbuf[buf], vbuf[buf]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [rows, span] f32, base-2
        if masked:
            col = first * block + i * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
            seen = col <= pos
            if window is not None:
                seen &= col > pos - window
            s = jnp.where(seen, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    if sink:  # the sink's term is in the sum before any key: exp2(m0 - m0) = 1
        m0 = jnp.concatenate(
            [jnp.full((tile, 1), sink_ref[h * G + g] * _LOG2E, jnp.float32) for g in range(G)]
        )
        l0 = jnp.ones((rows, 1), jnp.float32)
    else:
        m0 = jnp.full((rows, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((rows, 1), jnp.float32)
    carry = m0, l0, jnp.zeros((rows, Dv), jnp.float32)
    # Stretch i holds columns [c, c + span) from c = first * block + i * span.
    # Those that end at or before the tile's first position are seen whole
    # by every row, unless (a window) they begin before the last position's
    # window: the walk's first ones. A loop's carry (a tile's maxima, sums
    # and values: three values of [rows, 128] float32 as laid) is copied in
    # and out of it, so a kind has no more loops than it has use for: under a
    # window no longer than a tile every stretch is on a bound.
    origin = first * block
    masked, plain = (functools.partial(fold, masked=on_bound) for on_bound in (True, False))
    if window is not None and window <= tile:
        carry = jax.lax.fori_loop(0, n, masked, carry)
    else:
        whole = jnp.minimum((p0 - origin + 1) // span, n)  # the first stretch past p0
        lead = 0
        if window is not None:
            behind = p0 + tile - 1 - window - origin  # a column at or before it is not seen by all
            lead = jnp.minimum(jnp.where(behind >= 0, behind // span + 1, 0), n)
            whole = jnp.maximum(whole, lead)
            carry = jax.lax.fori_loop(0, lead, masked, carry)
        carry = jax.lax.fori_loop(lead, whole, plain, carry)
        carry = jax.lax.fori_loop(whole, n, masked, carry)
    _, l, acc = carry
    o_ref[0] = (acc * (1.0 / l)).reshape(G, tile, Dv).astype(o_ref.dtype)
    parity[0] = (begun + n) % 2


@functools.partial(jax.jit, static_argnames=("window", "interpret", "name"))
def paged_prefill_attention(
    q: jax.Array,  # [T, KH, group, Dk] — consecutive positions from ``start``
    pool_k: jax.Array,  # [L, N, KH, block, Dk or wider]
    pool_v: jax.Array,  # [L, N, KH, block, Dv]
    layer: jax.Array,  # scalar int32 — the layer of the pool to read
    table: jax.Array,  # [W] int32 block table
    start: jax.Array,  # scalar int32 — the first query's position
    n_keys: jax.Array,  # scalar int32, >= 1 — positions that hold a row by now
    sink: jax.Array | None = None,  # [KH, group] — the layer's learned sink a query head
    *,
    window: int | None = None,  # a query sees the last ``window`` positions, its own included
    interpret: bool = False,
    name: str = "paged_prefill_attention",  # the call's, in a device trace
) -> jax.Array:
    """softmax(q k^T / sqrt(Dk)) v of each query over the positions at or
    before its own (with ``window``, the last ``window`` of them; with
    ``sink``, its ``exp`` in the softmax's sum beside the keys') of the
    table's blocks in layer ``layer``, which already hold the queries' own
    keys and values; [T, KH, group, Dv] in the pool's dtype. Table entries
    past the block of the last position, and before the block that holds the
    window's first, are never read. A query at or past ``n_keys`` (the
    padding behind a last chunk) gets finite numbers that mean nothing."""
    T, KH, G, Dk = q.shape
    block, lanes, Dv = pool_k.shape[3], pool_k.shape[-1], pool_v.shape[-1]
    rows = tile(T, G, window)
    pages = stretch(rows, G, block, window) // block
    dtype = pool_k.dtype
    # Scaled here, in the pass that lays the query heads of a KV head beside
    # each other: the kernel's scores come out in base 2.
    q = (q.astype(jnp.float32) * (Dk**-0.5 * _LOG2E)).astype(dtype).transpose(1, 2, 0, 3)  # [KH, G, T, Dk]
    if lanes > Dk:  # zeros behind a key in the pool's rows meet zeros in the query
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, lanes - Dk),))
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    static = dict(block=block, pages=pages)
    if window is not None:
        static["window"] = window
    sinks = ()
    if sink is not None:
        static["sink"] = True
        sinks = (sink.astype(jnp.float32).reshape(-1),)
    scalars = jnp.stack([jnp.asarray(x, jnp.int32).reshape(()) for x in (layer, start, n_keys)])
    out = pl.pallas_call(
        functools.partial(_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(sinks),
            grid=(KH, T // rows),
            in_specs=[
                pl.BlockSpec((1, G, rows, lanes), lambda h, t, *_: (h, 0, t, 0)),
                anywhere,
                anywhere,
            ],
            out_specs=pl.BlockSpec((1, G, rows, Dv), lambda h, t, *_: (h, 0, t, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages * block, lanes), dtype),
                pltpu.VMEM((2, pages * block, Dv), dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((KH, G, T, Dv), dtype),
        # Tiles run in order: each starts the next one's first copies.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        name=name,
    )(scalars, table.astype(jnp.int32), *sinks, q, pool_k, pool_v)
    return out.transpose(2, 0, 1, 3)
