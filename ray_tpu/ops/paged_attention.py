"""Decode attention over a block pool, read where it lies.

One query position a slot attends that slot's *live* blocks only: a Pallas
TPU kernel walks the block table, copies each live block of the layer's keys
and values from the pool in HBM into VMEM (one contiguous block of every KV
head a copy, double-buffered a chunk of blocks at a time, the next slot's
first chunk in flight behind this slot's last) and folds the chunk into a
running (max, denominator, accumulator) online softmax in float32 — the
flash forward of ``ops/attention.py`` with the key axis taken from a table.
The work of a slot is ``ceil(length / block)`` blocks, whatever the table's
width: gathering a slot's whole table to a dense row, re-laying it and
masking most of it away costs ``max_seq`` a slot a layer, and at a table a
fifth full that was a third of the decode step.

The pool ``[L, N, KH, block, Dh]`` never becomes a value of the kernel's
caller: it is handed over whole in ``memory_space=ANY`` with the layer as a
prefetched scalar, so no layer's slab is sliced out as a temporary.

Two entries over one walk and one fold. :func:`paged_decode_attention`: keys
and values per head, two pools, two copies a live block.
:func:`paged_latent_decode_attention`: a pool of latent rows ``[L, N, block,
C]``, one row a position for all heads, which the absorbed query scores whole
and whose first ``value_width`` lanes are the values, so one copy of a live
block serves both: a single "KV head" whose group is every query head.

Matmuls run on the MXU in the pool's dtype with float32 accumulation;
scores, softmax statistics and the accumulator are float32; the mask is the
length (``col < length``, the gather path's ``col <= position``).

**A walk with a lower bound.** A layer that keeps only the last ``window``
positions (``window`` static, given to :func:`paged_decode_attention`) attends
columns ``length - window <= col < length``: a slot's walk then starts at the
block that holds ``length - window`` and the table's entries before it are
never read, so the engine may have given those blocks back. With no window the
kernel is traced as it was: the bound exists in the program only where asked.

**Keys wider than values, and a sink.** Each pool has a chunk buffer of its own
width, so keys of another width than the values' lie beside them, and a key
pool's rows may be wider than a key (zeros up to whole lane tiles: Mosaic copies
no slice of a row that is not whole tiles, so keys of 192 are stored in 256);
the scale of the scores is the key's own width's. A layer
with a learned *sink* (a scalar a query head that takes probability and adds no
value: ``p_j = exp(s_j) / (sum exp(s) + exp(sink))``) hands it over as a
float32 operand, and a slot's fold then starts from ``(sink, 1, 0)`` and not
from ``(-inf, 0, 0)``. Like the window, both exist in the program only where
asked: with one width and no sink the kernel is traced as it was.

**Heads of half a lane tile.** A head of 64 fills half the lanes of a tile, and
Mosaic copies no slice of a row that is not whole tiles. Such a head's value
and key lie side by side in ONE pool row of 128 lanes, ``[value | key]``
(:func:`paged_packed_decode_attention`): the walk is the latent rows' ("one
pool whose rows are the keys and, in their first lanes, the values") with a
row a KV head, the query laid against the key's lanes with zeros against the
value's, one copy a live block for both, and no lane of the pool is padding.

**A scale that is stated.** The scores' scale is ``Dk^-1/2`` unless the caller
states another (``scale``: a family whose published multiplier replaces it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _LOG2E, _NEG_INF

# Key positions folded into the softmax at a time. Live blocks are copied
# one by one, so a longer chunk saves loop turns and costs masked arithmetic
# on the dead end of a slot's last chunk: on a v5e, sixteen slots of 128-768
# positions take 49 us a layer at 128, 43 at 256 and 43 at 512 (PERF.md).
_CHUNK = 256
# The same for latent rows, whose slots hold five times the positions: on a
# v5e, thirty-two slots of 540-3,900 rows of 640 take 322 us a layer at 256,
# 279 at 512 and 261 at 1,024 (PERF.md section 6, PR 39).
_LATENT_CHUNK = 1024
# Query heads of one KV head are padded to the bf16 sublane tile, so that
# each head's [group, Dh] operand and [group, chunk] scores are whole tiles.
_GROUP_TILE = 16


# The chunk buffers (two a pool: four for keys and values, two for latent
# rows) may take this much of a core's VMEM (16 MiB by default on a v5e), the
# rest left to the scores.
_VMEM_BUFFER_BYTES = 8 * 2**20


def _pages(block_size: int, chunk: int) -> int:
    """Blocks a chunk: a block at least, however long."""
    return max(1, chunk // block_size)


def fits(
    kv_heads: int, head_dim: int, block_size: int, itemsize: int, value_dim: int | None = None
) -> bool:
    """Whether the kernel's copies and matmuls are whole TPU tiles at these
    shapes (the lane width in ``head_dim``, a key's stored width, and in
    ``value_dim``, a value's, where it is another; the bf16 sublane tile in
    the block) and its chunk buffers, of ``itemsize`` bytes an element, fit
    VMEM."""
    chunk = _pages(block_size, _CHUNK) * block_size
    value_dim = head_dim if value_dim is None else value_dim
    return (
        head_dim % 128 == 0
        and value_dim % 128 == 0
        and block_size % 16 == 0
        and 2 * kv_heads * chunk * (head_dim + value_dim) * itemsize <= _VMEM_BUFFER_BYTES
    )


def fits_latent(
    heads: int, row_dim: int, value_width: int, block_size: int, itemsize: int
) -> bool:
    """:func:`fits` for a pool of latent rows: the row and its value part in
    whole lane tiles, the block and the query heads (the one group) in whole
    bf16 sublane tiles, the two chunk buffers within VMEM."""
    chunk = _pages(block_size, _LATENT_CHUNK) * block_size
    return (
        row_dim % 128 == 0
        and value_width % 128 == 0
        and block_size % 16 == 0
        and heads % _GROUP_TILE == 0
        and 2 * chunk * row_dim * itemsize <= _VMEM_BUFFER_BYTES
    )


def _kernel(
    layer_ref, lengths_ref, tables_ref,  # scalar prefetch (SMEM)
    q_ref,  # [1, KH, G, Dh] VMEM
    *refs,  # (the sink,) the pools, o_ref, a chunk buffer for each pool, sems, parity
    block, pages, width, scale, window=None, sink=False,
):
    """``refs``: with ``sink``, first the sinks [KH, G, 1] float32 VMEM; the
    pools ``[L, N, KH, block, D]`` left in HBM, keys then values (each of its
    own width), or one whose rows are the keys and, in their first lanes, the
    values; ``o_ref`` [1, KH, G, Dv] VMEM; for each pool its chunk buffer
    [2, KH, pages * block, D] VMEM; DMA semaphores [pools, 2 (buffer)];
    SMEM [1]: the buffer the slot's first chunk was copied to. ``window``: the
    positions a slot's query sees, its own included (None: all of them)."""
    if sink:
        sink_ref, *refs = refs
    n = (len(refs) - 3) // 2
    pools, o_ref, bufs = refs[:n], refs[n], refs[n + 1 : 2 * n + 1]
    sems, parity = refs[2 * n + 1 :]
    kbuf, vbuf = bufs[0], bufs[-1]
    b = pl.program_id(0)
    slots = pl.num_programs(0)
    layer = layer_ref[0]
    chunk = pages * block

    def first_page(slot):
        """The table entry a slot's walk starts at: the block that holds the
        first position its window keeps."""
        return jnp.maximum(lengths_ref[slot] - window, 0) // block

    def live_pages(slot):
        """Blocks the slot's walk covers, from its first page on."""
        end = (lengths_ref[slot] + block - 1) // block
        return end if window is None else end - first_page(slot)

    def copies(slot, i, buf, j):
        """The copies of live block ``j`` of the slot's chunk ``i``, one a
        pool: every KV head of one block is contiguous in the pool, and
        lands strided, at its positions of each head's row of the buffer."""
        entry = i * pages + j if window is None else first_page(slot) + i * pages + j
        page = tables_ref[slot * width + entry]
        rows = pl.ds(pl.multiple_of(j * block, block), block)
        return [
            pltpu.make_async_copy(
                hbm.at[layer, page], dst.at[buf, :, rows, :], sems.at[t, buf]
            )
            for t, (hbm, dst) in enumerate(zip(pools, bufs))
        ]

    def for_each_copy(slot, i, buf, act):
        n = jnp.minimum(pages, live_pages(slot) - i * pages)

        def body(j, _):
            for c in copies(slot, i, buf, j):
                act(c)
            return _

        jax.lax.fori_loop(0, n, body, None)

    @pl.when(b == 0)
    def _first():
        # Rows past a slot's live blocks keep what an earlier chunk left
        # there; before any chunk that is whatever VMEM held, and a masked
        # probability of zero times a NaN is a NaN.
        vbuf[...] = jnp.zeros_like(vbuf)
        parity[0] = 0
        for_each_copy(0, 0, 0, lambda c: c.start())

    first = parity[0]
    length = lengths_ref[b]
    n_chunks = (live_pages(b) + pages - 1) // pages
    q = q_ref[0]  # [KH, G, Dh]
    KH, G, Dh = q.shape
    Dv = o_ref.shape[-1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (KH, G, chunk), 2)

    def body(i, carry):
        m, l, acc = carry
        buf = (first + i) % 2
        # The chunk after this one, of this slot or the first of the next,
        # goes into the other buffer while this one is attended.
        last = i + 1 == n_chunks
        nslot = jnp.where(last, b + 1, b)
        ni = jnp.where(last, 0, i + 1)

        @pl.when(nslot < slots)
        def _prefetch():
            for_each_copy(nslot, ni, 1 - buf, lambda c: c.start())

        for_each_copy(b, i, buf, lambda c: c.wait())
        k = kbuf[buf]  # [KH, chunk, Dh]
        # [KH, chunk, Dv]: all of a value buffer, the first lanes of a row's
        v = vbuf[buf, :, :, :Dv]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * (scale * _LOG2E)  # [KH, G, chunk] f32, base-2
        if window is None:
            seen = i * chunk + cols < length
        else:
            col = first_page(b) * block + i * chunk + cols
            seen = (col < length) & (col >= length - window)
        s = jnp.where(seen, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    if sink:  # the sink's term is in the sum before any key: exp2(m0 - m0) = 1
        m0 = sink_ref[...] * _LOG2E
        l0 = jnp.ones((KH, G, 1), jnp.float32)
    else:
        m0 = jnp.full((KH, G, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((KH, G, 1), jnp.float32)
    acc0 = jnp.zeros((KH, G, Dv), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    parity[0] = (first + n_chunks) % 2


def _attend(
    q, pools, layer, tables, lengths, *, value_width, scale, chunk, interpret, name, window=None,
    sink=None,
):
    """The call both entries make: ``q`` [B, KH, G, Dh] against ``pools``
    (each [L, N, KH, block, its own width]; the first one's rows are the keys,
    the last one's first ``value_width`` lanes the values), ``chunk``
    positions a fold, ``sink`` [KH, G] float32 or None; [B, KH, G,
    value_width]."""
    B, KH, G, Dh = q.shape
    block = pools[0].shape[3]
    pages = _pages(block, chunk)
    dtype = pools[0].dtype
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    static = dict(block=block, pages=pages, width=tables.shape[1], scale=scale)
    if window is not None:
        static["window"] = window
    sinks, sink_specs = (), ()
    if sink is not None:
        static["sink"] = True
        sinks = (sink.astype(jnp.float32)[:, :, None],)
        sink_specs = (pl.BlockSpec((KH, G, 1), lambda b, *_: (0, 0, 0)),)
    return pl.pallas_call(
        functools.partial(_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, KH, G, Dh), lambda b, *_: (b, 0, 0, 0)),
                *sink_specs,
                *[anywhere for _ in pools],
            ],
            out_specs=pl.BlockSpec((1, KH, G, value_width), lambda b, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                *[pltpu.VMEM((2, KH, pages * block, pool.shape[-1]), dtype) for pool in pools],
                pltpu.SemaphoreType.DMA((len(pools), 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KH, G, value_width), dtype),
        # Slots run in order: each starts the next one's first copies.
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        lengths.astype(jnp.int32),
        tables.reshape(-1).astype(jnp.int32),
        q.astype(dtype),
        *sinks,
        *pools,
    )


@functools.partial(jax.jit, static_argnames=("interpret", "window", "name", "scale"))
def paged_decode_attention(
    q: jax.Array,  # [B, KH, group, Dk] — one query position a slot
    pool_k: jax.Array,  # [L, N, KH, block, Dk or wider]
    pool_v: jax.Array,  # [L, N, KH, block, Dv]
    layer: jax.Array,  # scalar int32 — the layer of the pool to read
    tables: jax.Array,  # [B, W] int32 block tables
    lengths: jax.Array,  # [B] int32, >= 1 — positions attended, a slot
    sink: jax.Array | None = None,  # [KH, group] — the layer's learned sink a query head
    *,
    interpret: bool = False,
    window: int | None = None,  # of them, only the last ``window``
    name: str = "paged_decode_attention",  # the call's, in a device trace
    scale: float | None = None,  # of the scores (None: Dk^-1/2)
) -> jax.Array:
    """softmax(q k^T / sqrt(Dk)) v over each slot's first ``lengths[b]``
    positions of its table's blocks in layer ``layer`` (with ``window``, the
    last ``window`` of them; with ``sink``, its ``exp`` in the softmax's sum
    beside the keys'); [B, KH, group, Dv] in the pool's dtype. Table entries
    past a slot's live blocks, and before the block that holds the window's
    first position, are never read. A key pool whose rows are wider than
    ``Dk`` (zeros behind a key, up to whole lane tiles) meets zeros in the
    query; the scale is the key's own width's."""
    G, Dk = q.shape[2:]
    pad, lanes = -G % _GROUP_TILE, pool_k.shape[-1] - Dk
    if pad or lanes:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, lanes)))
    if pad and sink is not None:
        sink = jnp.pad(sink, ((0, 0), (0, pad)))
    out = _attend(
        q, (pool_k, pool_v), layer, tables, lengths, value_width=pool_v.shape[-1],
        scale=Dk**-0.5 if scale is None else scale, chunk=_CHUNK, interpret=interpret, name=name,
        window=window, sink=sink,
    )
    return out[:, :, :G]


def fits_packed(kv_heads: int, head_dim: int, block_size: int, itemsize: int) -> bool:
    """:func:`fits` for a pool whose row is a head's ``[value | key]``: the
    two halves make one whole lane tile, the block whole bf16 sublane tiles,
    the two chunk buffers within VMEM."""
    chunk = _pages(block_size, _CHUNK) * block_size
    return (
        (2 * head_dim) % 128 == 0
        and block_size % 16 == 0
        and 2 * kv_heads * chunk * 2 * head_dim * itemsize <= _VMEM_BUFFER_BYTES
    )


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_packed_decode_attention(
    q: jax.Array,  # [B, KH, group, Dh] — one query position a slot
    pool: jax.Array,  # [L, N, KH, block, 2 Dh]: a position's [value | key] a KV head
    layer: jax.Array,  # scalar int32 — the layer of the pool to read
    tables: jax.Array,  # [B, W] int32 block tables
    lengths: jax.Array,  # [B] int32, >= 1 — positions attended, a slot
    *,
    scale: float | None = None,  # of the scores (None: Dh^-1/2)
    interpret: bool = False,
) -> jax.Array:
    """softmax(q k^T scale) v over each slot's first ``lengths[b]`` positions
    of its table's blocks in layer ``layer``, keys and values read from the one
    pool; [B, KH, group, Dh] in the pool's dtype. The query meets zeros where
    a row holds its value, and the fold's product with the whole row is cut to
    the value's lanes behind the call: every operand of the kernel is whole
    lane tiles."""
    B, KH, G, Dh = q.shape
    q = jnp.pad(q, ((0, 0), (0, 0), (0, -G % _GROUP_TILE), (pool.shape[-1] - Dh, 0)))
    out = _attend(
        q, (pool,), layer, tables, lengths, value_width=pool.shape[-1],
        scale=Dh**-0.5 if scale is None else scale, chunk=_CHUNK, interpret=interpret,
        name="paged_decode_attention_packed",
    )
    return out[:, :, :G, :Dh]


@functools.partial(jax.jit, static_argnames=("value_width", "scale", "interpret"))
def paged_latent_decode_attention(
    ql: jax.Array,  # [B, H, C] — the absorbed query of one position a slot
    pool: jax.Array,  # [L, N, block, C] latent rows
    layer: jax.Array,  # scalar int32 — the layer of the pool to read
    tables: jax.Array,  # [B, W] int32 block tables
    lengths: jax.Array,  # [B] int32, >= 1 — positions attended, a slot
    *,
    value_width: int,  # a row's first lanes that are its value
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """softmax(ql rows^T scale) rows[:, :value_width] over each slot's first
    ``lengths[b]`` rows of its table's blocks in layer ``layer``; [B, H,
    value_width] in the pool's dtype. Table entries past a slot's live
    blocks are never read."""
    out = _attend(
        ql[:, None], (pool[:, :, None],), layer, tables, lengths,
        value_width=value_width, scale=scale, chunk=_LATENT_CHUNK,
        interpret=interpret, name="paged_latent_decode_attention",
    )
    return out[:, 0]
