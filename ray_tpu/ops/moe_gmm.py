"""The experts' grouped matrix product, each touched expert's weights read once.

``lhs`` [m, K] holds rows sorted by group, ``rhs`` [E, K, N] a matrix a
group, ``group_sizes`` [E] how many consecutive rows each group owns; row
``r`` of the result is ``lhs[r] @ rhs[group of r]``: ``jax.lax.ragged_dot``'s
contract (operands in their dtype, float32 accumulation, the result in
``lhs``'s dtype), and rows behind the last group come back zero.

An expert layer at serving time is bound by its weights' bytes a hundred
times over: a decode step puts one to three rows on each of a hundred
experts of 5-29 MB. So the kernel is a walk over *visits*: the rows go in
tiles of ``_ROWS``, a visit is one (row tile, group) pair that shares a row,
and the visits in row order are computed outside the kernel from the group
sizes and handed over as prefetched scalars (:func:`_visits`). The grid is
(column tiles of the weights, visits); the weight tile ``[K, tn]`` of a
visit's group is brought into VMEM by the pipeline, double-buffered, while
the visit before it multiplies. What that buys:

- **A group of size zero has no visit**, so an untouched expert costs no
  byte, and a pass over ``ROWS_A_PASS`` sorted rows reads the experts it
  reaches and no other.
- **A group that straddles row tiles is read once**: its visits are
  consecutive, the weight tile's index does not change between them, and
  the pipeline does not copy a block it already holds.
- **Rows behind the last group are zero**: every row tile has a visit, the
  tiles past the last row at the end of the walk under the last group's
  number (so they copy nothing), and the first visit of a tile zeroes it
  before the visit's group writes the rows it owns.

The tile's width is the widest whole-lane divisor of ``N`` whose ``[K, tn]``
tile stays under ``_TILE_BYTES`` (all of ``N`` for a matrix of 5 MB: one
contiguous copy an expert), from the shapes alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows a visit multiplies: the MXU's height. Fewer rows cost the MXU the
# same (loading a 128 x 128 weight tile takes what streaming 128 rows
# through it takes) and make more visits: 32 reads within 1% of 128 at a
# decode step's rows and 8-15% slower in a pass of 2,048 (v5e, PERF.md
# section 6, PR 36); more rows double the arithmetic of every visit for the
# one to fifty rows it owns.
_ROWS = 128
# One weight tile [K, tn]; the pipeline holds two. 4 MiB reads 1-6% slower
# than 8, 16 the same (ibid.).
_TILE_BYTES = 8 * 2**20
# What the call may take of a core's VMEM (128 MiB on a v5e; the compiler's
# default scope is 16): two weight tiles, two row tiles of 7,168 columns,
# two output tiles and the float32 product.
_VMEM_LIMIT_BYTES = 48 * 2**20


def _tile_n(k: int, n: int, itemsize: int) -> int:
    """Columns of a weight tile: the widest divisor of ``n`` in whole
    128-lane tiles whose ``[k, tn]`` stays under ``_TILE_BYTES``, 0 where
    not even one lane tile does; all of ``n`` where it is no whole lane
    tiles (the interpreter takes any shape; :func:`fits` keeps such a
    width off the chip)."""
    if n % 128:
        return n
    lanes = n // 128
    for d in range(lanes, 0, -1):
        if lanes % d == 0 and k * d * 128 * itemsize <= _TILE_BYTES:
            return d * 128
    return 0


def fits(k: int, n: int, dtype, mesh=None) -> bool:
    """Whether a grouped product ``[m, k] x [E, k, n]`` built in this
    process runs the kernel: on a TPU, for ``k`` and ``n`` in whole lane
    tiles whose weight tile fits VMEM, outside a mesh of more than one chip
    (the compiler cannot partition a Mosaic call); ``jax.lax.ragged_dot``
    otherwise. Decided by what the code can see, like
    ``paged.decode_attends_in_place``; nothing a user sets reaches it."""
    return (
        jax.default_backend() == "tpu"
        and (mesh is None or mesh.size == 1)
        and k % 128 == 0
        and n % 128 == 0
        and _tile_n(k, n, jnp.dtype(dtype).itemsize) > 0
    )


def _visits(group_sizes, m_tiles: int, tm: int):
    """The walk over ``m_tiles`` row tiles of ``tm`` rows: ``(groups [L],
    tiles [L], offsets [E + 1], count)``. Visit ``v < count`` multiplies row
    tile ``tiles[v]`` by group ``groups[v]``, whose rows are ``offsets[g]``
    to ``offsets[g + 1]``. A group with rows visits each tile it has a row
    in, in row order; then each tile past the last row is visited once,
    under the last visited group's number, where it owns nothing. ``L = E +
    m_tiles - 1`` bounds the count: every visit but a tile's first begins a
    group."""
    E = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    each = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(each)
    real = upto[-1]
    v = jnp.arange(E + m_tiles - 1, dtype=jnp.int32)
    g = jnp.minimum(jnp.sum(v[:, None] >= upto[None, :], axis=1), E - 1)
    tile = first[g] + v - (upto[g] - each[g])
    behind = v >= real
    last = jnp.max(jnp.where(group_sizes > 0, jnp.arange(E), 0))
    live_tiles = (ends[-1] + tm - 1) // tm
    groups = jnp.where(behind, last, g)
    tiles = jnp.clip(jnp.where(behind, live_tiles + v - real, tile), 0, m_tiles - 1)
    offsets = jnp.concatenate([jnp.zeros(1, ends.dtype), ends])
    return (
        groups.astype(jnp.int32), tiles.astype(jnp.int32), offsets.astype(jnp.int32),
        (real + m_tiles - live_tiles).astype(jnp.int32),
    )


def _kernel(groups, tiles, offsets, x_ref, w_ref, o_ref, *, tm):
    v = pl.program_id(1)
    g, t = groups[v], tiles[v]
    lo, hi = offsets[g], offsets[g + 1]
    row0 = t * tm

    # The tile's first visit: what no group owns is zero, not what VMEM held.
    @pl.when(jnp.logical_or(v == 0, tiles[jnp.maximum(v - 1, 0)] != t))
    def _fresh():
        o_ref[...] = jnp.zeros_like(o_ref)

    # A tile behind the last row is visited under a group that owns none of it.
    @pl.when(jnp.logical_and(hi > row0, lo < row0 + tm))
    def _owns():
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        y = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
        mine = jnp.logical_and(rows >= lo, rows < hi)
        o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def gmm(
    lhs: jax.Array,  # [m, K] rows sorted by group
    rhs: jax.Array,  # [E, K, N]
    group_sizes: jax.Array,  # [E] int32, summing to m at most
    *,
    interpret: bool = False,
) -> jax.Array:
    """``lhs[rows of group g] @ rhs[g]`` for every group, [m, N] in ``lhs``'s
    dtype with float32 accumulation; rows behind the last group are zero."""
    m, K = lhs.shape
    E, _, N = rhs.shape
    tm = min(_ROWS, -(-m // 16) * 16)
    tn = _tile_n(K, N, rhs.dtype.itemsize)
    if not tn:
        raise ValueError(f"a weight tile [{K}, 128] of {rhs.dtype} exceeds {_TILE_BYTES} bytes")
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    m_tiles = (m + pad) // tm
    groups, tiles, offsets, count = _visits(group_sizes.astype(jnp.int32), m_tiles, tm)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // tn, count),
            in_specs=[
                pl.BlockSpec((tm, K), lambda n, v, groups, tiles, offsets: (tiles[v], 0)),
                pl.BlockSpec((None, K, tn), lambda n, v, groups, tiles, offsets: (groups[v], 0, n)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n, v, groups, tiles, offsets: (tiles[v], n)),
        ),
        out_shape=jax.ShapeDtypeStruct((m + pad, N), lhs.dtype),
        # Visits run in order: a row tile's visits are consecutive, and its
        # output block stays in VMEM between them.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="moe_gmm",
    )(groups, tiles, offsets, lhs, rhs)
    return out[:m] if pad else out
