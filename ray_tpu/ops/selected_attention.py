"""Latent attention under a mask of selected positions, one Pallas call a
layer: the call walks the table's live stretches itself, expands a head's
keys and values from the latent rows on the chip, and keeps the running
softmax there from the first stretch to the last.

A prefill chunk of a model with an indexer attends, a query, the positions
its indexer kept: no stretch of the table can be skipped (2,048 queries'
choices cover it) and no row can be fetched by index at the chip's speed, so
attention runs over expanded keys and values under the selection's mask, a
stretch of the table at a time (``models/deepseek_v32.py:attend_selected``).
In XLA's own operations a step of that fold writes the float32 scores ``[H,
queries, stretch]`` to HBM and reads them back three times (PERF.md section 6,
PR 56); as one call a stretch (PR 56's kernel) the scores stayed on the chip
but the running softmax of every head, the queries, the mask as a bfloat16
bias and the expanded keys and values went through HBM once a stretch, 855 MB
beside 0.87 ms of products (PERF.md section 6, PR 57).

:func:`attend` is the whole fold as one call over a grid of head groups
(``_HEADS`` heads a cell). What a cell reads from HBM: its heads' queries
and their columns of ``wkvb`` once (blocks, pipelined behind the cell before),
and, a live stretch, the stretch's latent rows through the block table and
the mask's ``[queries, stretch]`` tile at a byte an entry, both copied by the
cell itself into two buffers each, the next stretch's in flight behind this
one's products (the next cell's first behind a cell's last). What stays in
VMEM: a head's keys ``[k_n; k_r]`` and values of the stretch, made once a
(head, stretch) from the rows (``c W`` with float32 accumulation, rounded to
the rows' dtype as the fold's einsum rounds them; the rotated shared key is
stored behind every head's own, so a score is one product over 192), the
scores of a tile of queries, and maximum, sum and weighted values of all the
cell's queries, written out once, normalised, as ``[queries, heads x values]``.
The number of live stretches is an operand: one program for every start, and
a chunk at start 0 walks two stretches, not the table's thirty-four.

VMEM at ``_HEADS`` 4, 2,048 queries in tiles of 512, a stretch of 1,024,
rows of 640 and keys of 192: the queries' block 3 MB and the output's 2 MB,
twice each for the pipeline, ``wkvb``'s 1 MB twice; the queries by head 4 MB
(192 in 256 lanes); the running softmax 12 MB (maximum, sum and weighted
values, 128 lanes each); two buffers of rows 2.6 MB and of mask 4 MB; the
cell's keys and values 3 MB; four heads' scores of a tile and their
exponentials about 12 MB: under 52 MB of the 64 this file asks for (a v5e
core has 128 MiB).

The same arithmetic as the fold: operands in the activation dtype, float32
scores, maximum, sum and accumulation, the weights cast to the values' dtype
before the second product.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _LOG2E

_F32 = jnp.float32
# Heads a grid cell, and the queries a tile of scores (the most of these that
# divides the run): PERF.md section 6, PR 57, has the sizes tried.
_HEADS = 4
_QUERIES = (512, 256)
_VMEM_LIMIT_BYTES = 64 * 2**20
NAME = "selected_attention_fold"


def fits(
    heads: int, queries: int, keys: int, value_width: int, dtype, mesh=None, *,
    nope: int = 128, rank: int = 512, block_size: int = 16,
) -> bool:
    """Whether :func:`attend` takes these shapes on a TPU: heads and queries
    in whole cells and tiles, a stretch of ``keys`` positions, a head's own
    key (``nope``), its values and the latent part of a row (``rank``) in
    whole lane tiles, blocks of whole sublane tiles, two-byte operands, no
    mesh over chips (the compiler cannot partition a Mosaic call)."""
    return (
        (mesh is None or mesh.size == 1)
        and heads % _HEADS == 0 and queries % _QUERIES[-1] == 0
        and keys % 128 == 0 and value_width % 128 == 0
        and nope % 128 == 0 and rank % 128 == 0 and block_size % 16 == 0
        and jnp.dtype(dtype).itemsize == 2
    )


def _kernel(
    scalars, table,  # scalar prefetch (SMEM): [layer, live stretches]; [W]
    q_ref, w_ref, pool, keep, o_ref,
    qh, kbuf, vbuf, top, total, acc, rows, mask, sems,
    *, scale, tile, nope, rank,
):
    """``q_ref`` [T, G (d_n + d_r)] and ``w_ref`` [R, G (d_n + d_v)]: the
    cell's heads side by side as the model lays them; ``pool`` [L, N, block,
    C] and ``keep`` [T, S] int8 left in HBM; ``o_ref`` [T, G d_v]. Scratch:
    the queries by head (``qh`` [G, T, d_n + d_r]: a head's 192 columns begin
    at no lane tile, so they are cut from the block once a cell, not once a
    product), the cell's keys and values of the stretch (``kbuf`` [G, Kb, d_n
    + d_r], ``vbuf`` [G, Kb, d_v]), the running softmax (``top`` and ``total``
    [G, T, 128] float32, a row's maximum and its sum the same in every lane:
    a ``[G, T, 1]`` array is laid out in whole lane tiles all the same, and
    a statistic that fills its tile meets scores and values with no lane
    picked out and spread again; ``acc`` [G, T, d_v]), two buffers of rows
    [2, Kb, C] and of mask [2, T, Kb], DMA semaphores [2 (rows, mask), 2
    (buffer)]."""
    h, cells = pl.program_id(0), pl.num_programs(0)
    layer, n = scalars[0], scalars[1]
    G, T, dk = qh.shape
    _, Kb, dv = vbuf.shape
    block = pool.shape[2]
    pages, tiles = Kb // block, T // tile
    lanes = top.shape[2]

    def across(x, width):  # a statistic, the same in every lane, as wide as what it meets
        return x if width == lanes else pltpu.repeat(x, width // lanes, axis=1)

    def rows_of(i):
        return pl.ds(pl.multiple_of(i * tile, tile), tile)

    def begin(j, buf):
        """Start the copies of stretch ``j``: its blocks through the table,
        and its columns of the mask."""

        def page(b, _):
            pltpu.make_async_copy(
                pool.at[layer, table[j * pages + b]],
                rows.at[buf, pl.ds(pl.multiple_of(b * block, block), block), :],
                sems.at[0, buf],
            ).start()
            return _

        jax.lax.fori_loop(0, pages, page, None)
        pltpu.make_async_copy(
            keep.at[:, pl.ds(pl.multiple_of(j * Kb, Kb), Kb)], mask.at[buf], sems.at[1, buf]
        ).start()

    def finish(buf):
        # Every copy signals its semaphore by its bytes: one wait for a whole
        # buffer's takes a stretch's blocks all.
        for p, vmem in enumerate((rows, mask)):
            pltpu.make_async_copy(vmem.at[buf], vmem.at[buf], sems.at[p, buf]).wait()

    @pl.when(h == 0)
    def _first():
        begin(0, 0)

    def lay(i, _):
        """A tile of the cell's queries by head, and a running softmax that
        has seen nothing."""
        r = rows_of(i)
        for g in range(G):
            qh[g, r, :] = q_ref[r, g * dk : (g + 1) * dk]
        top[:, r, :] = jnp.full((G, tile, lanes), -1e30, _F32)
        total[:, r, :] = jnp.zeros((G, tile, lanes), _F32)
        acc[:, r, :] = jnp.zeros((G, tile, dv), _F32)
        return _

    jax.lax.fori_loop(0, tiles, lay, None)
    # exp(scale (s - m)) as exp2((s - m) c): the scores stay as the product
    # leaves them (a row's maximum is of those) and the scale rides in the
    # multiplication an exponential has
    c = scale * _LOG2E

    def stretch(j, _):
        t = h * n + j  # stretches begun before this one, all cells: its buffer
        buf = t % 2

        @pl.when(t + 1 < cells * n)
        def _next():  # the stretch after this one, or the next cell's first
            begin(jnp.where(j + 1 == n, 0, j + 1), 1 - buf)

        finish(buf)
        for g in range(G):  # a head's keys, the shared key behind them, and its values: once for all the queries
            kv = jax.lax.dot_general(
                rows[buf, :, :rank], w_ref[:, g * (nope + dv) : (g + 1) * (nope + dv)],
                (((1,), (0,)), ((), ())), preferred_element_type=_F32,
            ).astype(kbuf.dtype)
            kbuf[g, :, :nope] = kv[:, :nope]
            kbuf[g, :, nope:] = rows[buf, :, rank : rank + dk - nope]
            vbuf[g] = kv[:, nope:]

        def fold(i, _):
            r = rows_of(i)
            for g in range(G):  # unrolled: one head's softmax runs beside the next one's products
                s = jax.lax.dot_general(
                    qh[g, r, :], kbuf[g], (((1,), (1,)), ((), ())), preferred_element_type=_F32
                )
                # (the tile's bytes are read again a head: a mask held for four heads is 256 registers' worth of spills)
                s = jnp.where(mask[buf, r, :] != 0, s, -1e30)
                m_prev = top[g, r, :]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                alive = jnp.exp2((m_prev - m_new) * c)
                e = jnp.exp2((s - across(m_new, Kb)) * c)
                top[g, r, :] = m_new
                total[g, r, :] = alive * total[g, r, :] + jnp.sum(e, axis=1, keepdims=True)
                acc[g, r, :] = across(alive, dv) * acc[g, r, :] + jax.lax.dot_general(
                    e.astype(vbuf.dtype), vbuf[g], (((1,), (0,)), ((), ())), preferred_element_type=_F32
                )
            return _

        return jax.lax.fori_loop(0, tiles, fold, _)

    jax.lax.fori_loop(0, n, stretch, None)

    def out(i, _):
        r = rows_of(i)
        for g in range(G):
            o_ref[r, g * dv : (g + 1) * dv] = (acc[g, r, :] / across(total[g, r, :], dv)).astype(o_ref.dtype)
        return _

    jax.lax.fori_loop(0, tiles, out, None)


@functools.partial(jax.jit, static_argnames=("scale", "nope", "pages", "interpret"))
def attend(q, wkvb, pool, layer, table, keep, steps, *, scale: float, nope: int, pages: int, interpret: bool = False):
    """softmax(``scale`` q [k_n; k_r]^T) v over the positions ``keep`` [T, W
    block] bool names, [T, H, d_v] in the queries' dtype: ``q`` [T, H, d_n +
    d_r] (``nope`` is ``d_n``) against layer ``layer`` of the latent pool
    ``pool`` [L, N, block, R + d_r or wider] through ``table`` [W], a head's
    keys and values expanded from a row's first ``R`` values by its columns
    of ``wkvb`` [R, H (d_n + d_v)] and the row's next ``d_r``, the rotated
    shared key, behind every head's own. The first ``steps`` (traced)
    stretches of ``pages`` blocks are walked and nothing behind them is read;
    a table entry names a block of the pool (it is held to the pool's).
    A row that keeps nothing of a stretch while its maximum is still -1e30
    gathers ``exp(0)`` a column, which the first stretch that holds one of
    its positions multiplies away, as in the fold. Jitted, so that a
    program's layers share one trace and one lowering of the call."""
    T, H, dk = q.shape
    R = wkvb.shape[0]
    dv = wkvb.shape[1] // H - nope
    block, C = pool.shape[2:]
    G, Kb = math.gcd(H, _HEADS), pages * block
    tile = next((t for t in _QUERIES if T % t == 0), T)
    lanes = math.gcd(128, Kb, dv)  # of a row's maximum and sum, each the same in every lane
    steps = jnp.minimum(steps, table.shape[0] // pages)
    scalars = jnp.stack([jnp.asarray(x, jnp.int32).reshape(()) for x in (layer, steps)])
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, tile=tile, nope=nope, rank=R),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(H // G,),
            in_specs=[
                pl.BlockSpec((T, G * dk), lambda h, *_: (0, h)),
                pl.BlockSpec((R, G * (nope + dv)), lambda h, *_: (0, h)),
                anywhere,
                anywhere,
            ],
            out_specs=pl.BlockSpec((T, G * dv), lambda h, *_: (0, h)),
            scratch_shapes=[
                pltpu.VMEM((G, T, dk), q.dtype),
                pltpu.VMEM((G, Kb, dk), q.dtype),
                pltpu.VMEM((G, Kb, dv), q.dtype),
                pltpu.VMEM((G, T, lanes), _F32),
                pltpu.VMEM((G, T, lanes), _F32),
                pltpu.VMEM((G, T, dv), _F32),
                pltpu.VMEM((2, Kb, C), pool.dtype),
                pltpu.VMEM((2, T, Kb), jnp.int8),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((T, H * dv), q.dtype),
        # Cells run in order: each starts the next one's first copies.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        name=NAME,
    )(
        scalars, jnp.clip(table, 0, pool.shape[1] - 1).astype(jnp.int32),
        q.reshape(T, H * dk), wkvb, pool, keep.astype(jnp.int8),
    )
    return out.reshape(T, H, dv)
