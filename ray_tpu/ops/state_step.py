"""One decode step of a recurrent state a slot, the tile held on the chip.

A family that keeps a float32 state a slot (``models/paged.py``, "a state and
a tail per slot") steps every live slot's state once a decode step, and the
step is bound by the state's bytes: a dozen vector operations an element on
tiles of 32-64 KB a head. In plain ``jax.numpy`` the reductions that read the
state and the update that writes it are separate passes over HBM by data
dependence (:func:`ray_tpu.ops.delta_rule.kda_step` needs all of ``S . k``
before it can write, and ``S . q`` reads what it wrote). Here a ``(row, head
group)`` block of the pool's own ``state`` ``[layers, slots + 1, H, a, b]`` is
brought into VMEM, stepped whole, and written back to the place it came from:
one read and one write a layer, the output aliased to the input so that no
slab is copied.

Two tile bodies on the one frame (:func:`_call`):

- :func:`kda`, the gated delta rule with a decay a key channel
  (``kda_step``'s mathematics) on a tile ``S`` ``[d_k, d_v]``::

      S <- exp(g) S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q

- :func:`ssd`, Mamba-2's step (``ssd_step``'s) on a tile ``h`` ``[P, N]``,
  ``B`` and ``C`` shared by a group's heads::

      h <- exp(dt A) h + (x dt) B^T;  y = h C + D x

Everything is float32 and every product exact, as the plain forms' ``HIGHEST``
gives: nothing goes through the matrix unit but one transposition a block.
The small operands come in as they lie, ``[rows, H, d]`` with ``d`` along
lanes; what must run along a tile's sublanes (``q``, ``k``, ``exp(g)``; ``x
dt``, the decay) is stacked and turned once a block on the chip, and a
head's column is a lane of the result. A number a head (``beta``; ``exp(dt
A)``) is folded into, or laid beside, those rows by the caller's fusion
before the call: :func:`kda` computes ``u`` as ``beta v - S^T (beta k)``.

``keep`` [rows] bool marks the rows that are not live: their state stays as
it was, bit for bit, and no byte of it moves (the grid takes the live rows
first: :func:`_rows`); the kernel's output for them, which means nothing, is
zero. Rows past ``rows``
(the scratch row) and the other layers are no block of the grid and are never
touched.

:class:`Rows` is what :func:`ray_tpu.models.paged.state_decode` hands a
family's step in place of the rows' values where the kernel runs; a step that
is given arrays gets the plain form.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.delta_rule import kda_step
from ray_tpu.ops.ssd import ssd_step

_F32 = jnp.float32
# One block of the state, a row's head group: the pipeline holds two coming
# in and two going out.
_BLOCK_BYTES = 2 * 2**20
# What the call may take of a core's VMEM (128 MiB on a v5e; the compiler's
# default scope is 16): the four blocks, the small operands and a tile's
# temporaries.
_VMEM_LIMIT_BYTES = 32 * 2**20
_LANES, _SUBLANES = 128, 8


@dataclasses.dataclass(frozen=True)
class Rows:
    """Rows ``[:rows]`` of layer ``layer`` of a pool's ``state`` ``[layers,
    slots + 1, H, a, b]``, where they lie. ``keep`` [rows] bool or None (every
    row is live). :func:`kda` and :func:`ssd` given one return one, its
    ``state`` the pool's after the step."""

    state: jax.Array
    layer: int
    rows: int
    keep: Optional[jax.Array] = None
    interpret: bool = False


def _whole_group(heads: int, a: int, b: int) -> int:
    """The most heads that divide ``heads``, in whole sublane tiles (the small
    operands' blocks are ``[group, d]``), whose ``[group, a, b]`` float32
    stays under ``_BLOCK_BYTES``; 0 where no count of heads does."""
    return next(
        (g for g in range(heads, 0, -1)
         if heads % g == 0 and g % _SUBLANES == 0 and g * a * b * 4 <= _BLOCK_BYTES),
        0,
    )


def head_group(heads: int, a: int, b: int) -> int:
    """Heads of a block: :func:`_whole_group`, and all the heads where there
    is none (the interpreter takes any shape; :func:`tiles` keeps such a
    shape off the chip)."""
    return _whole_group(heads, a, b) or heads


def tiles(heads: int, a: int, b: int) -> bool:
    """Whether a state of ``heads`` tiles ``[a, b]`` has the kernel's shapes:
    tiles in whole ``(8, 128)`` float32 tiles, no taller than the one
    transposition (128 columns), and a head group in whole sublane tiles
    that fits VMEM."""
    return a % _SUBLANES == 0 and a <= _LANES and b % _LANES == 0 and _whole_group(heads, a, b) > 0


def fits(heads: int, a: int, b: int, mesh=None) -> bool:
    """Whether a decode program built in this process steps such a state
    through the kernel: on a TPU, outside a mesh of more than one chip (the
    compiler cannot partition a Mosaic call), at shapes that :func:`tiles`.
    Decided by what the code can see, like ``ops.moe_gmm.fits``; nothing a
    user sets reaches it."""
    return (
        jax.default_backend() == "tpu"
        and (mesh is None or mesh.size == 1)
        and tiles(heads, a, b)
    )


def _turned(rows, height: int):
    """``rows``: arrays ``[n_i, d]`` with ``d <= 128``. Their stack, turned:
    ``[height, sum n_i (padded to whole lane tiles)]``, column ``c`` the
    stack's row ``c`` along sublanes. One aligned ``[128 m, 128]``
    transposition."""
    stack = jnp.concatenate(rows, axis=0)
    n, d = stack.shape
    stack = jnp.pad(stack, ((0, -n % _LANES), (0, _LANES - d)))
    return stack.T[:height]


def _kda_tile(_, q_ref, k_ref, kb_ref, g_ref, vb_ref, s_ref, o_ref, s_out_ref):
    """A live row's head group (whichever): ``q, k, kb, g`` [G, d_k] (``kb =
    beta k``), ``vb`` [G, d_v] (``beta v``), ``s`` [G, d_k, d_v]."""
    G, dk, _ = s_ref.shape
    cols = _turned([q_ref[...], k_ref[...], kb_ref[...], jnp.exp(g_ref[...])], dk)
    for h in range(G):
        q, k, kb, decay = (cols[:, i * G + h : i * G + h + 1] for i in range(4))  # [d_k, 1]
        S = decay * s_ref[h]
        u = vb_ref[h : h + 1, :] - jnp.sum(kb * S, axis=0, keepdims=True)  # [1, d_v]
        S = S + k * u
        s_out_ref[h] = S
        o_ref[h : h + 1, :] = jnp.sum(q * S, axis=0, keepdims=True)


def _ssd_tile(j, xdt_ref, decay_ref, b_ref, c_ref, h_ref, y_ref, h_out_ref, ys_ref, *, heads_a_group):
    """A live row's head group ``j``: ``xdt`` [G, P]; ``decay`` [G, P], the head's
    ``exp(dt A)`` on every lane; ``b``, ``c`` [groups, N] (all of the row's);
    ``h`` [G, P, N]; ``y`` [G, P]; ``ys`` [P, >= G], the outputs as columns."""
    G, P, N = h_ref.shape
    first = j * G  # the block's first head
    cols = _turned([xdt_ref[...], decay_ref[...]], P)  # [P, >= 2 G]
    ones = jnp.ones((N, _LANES), _F32)
    for h in range(G):
        group = (first + h) // heads_a_group
        B, C = b_ref[pl.ds(group, 1), :], c_ref[pl.ds(group, 1), :]  # [1, N]
        xdt = cols[:, h : h + 1]  # [P, 1]
        # The same number down the column: one tile of it serves the head's.
        decay = jnp.tile(
            jnp.broadcast_to(cols[:_SUBLANES, G + h : G + h + 1], (_SUBLANES, N)),
            (-(-P // _SUBLANES), 1),
        )[:P]
        t = decay * h_ref[h] + xdt * B
        h_out_ref[h] = t
        # A row's sum, on every lane of the product: through the matrix unit,
        # where a product with one is exact and the sum float32 (a reduction
        # along lanes on the vector unit takes longer than the tile's bytes).
        ys_ref[:, h : h + 1] = jnp.dot(
            t * C, ones, preferred_element_type=_F32, precision=jax.lax.Precision.HIGHEST
        )[:, :1]
    y_ref[...] = jnp.pad(ys_ref[...], ((0, _LANES - P), (0, 0))).T[:G, :P]


def _rows(tile, operands: int):
    """``tile(head group, *refs)``, written for a live row, under the grid's
    rule for every row. The prefetched scalars: ``order`` [rows], the rows as
    the grid takes them, the live ones first; ``at`` [rows], the row whose
    block of the state a step holds; ``live`` [1], how many are live;
    ``layer`` [1], for the index maps. A kept
    row comes behind the live ones and rides on the last one's block: the
    pipeline neither reads nor writes a block whose index does not change, so
    no byte of a kept row's state moves, and nothing waits between two live
    rows. Where no row is live each holds its own block and puts it through."""

    def kernel(order_ref, at_ref, live_ref, layer_ref, *refs):
        state_ref, out_ref, state_out_ref = refs[operands : operands + 3]
        j, t = pl.program_id(0), pl.program_id(1)

        @pl.when(t < live_ref[0])
        def _live():
            tile(j, *refs)

        @pl.when(t >= live_ref[0])
        def _kept():
            out_ref[...] = jnp.zeros_like(out_ref)

            @pl.when(at_ref[t] == order_ref[t])
            def _through():
                state_out_ref[...] = state_ref[...]

    return kernel


def _call(tile, name, state, layer, keep, G, operands, specs, out_width, scratch, interpret):
    """The frame: ``tile`` over the grid (group of ``G`` heads, row) of rows
    ``[:len(keep)]`` of layer ``layer`` (a traced scalar: every layer of a
    program is the one trace, lowered once) of ``state``, a head group's rows
    one after another. ``operands`` the small operands, ``specs`` their
    blocks. Returns ``(out [rows, H, out_width] float32, state)``, the state
    the operand's own buffer where the caller donated it."""
    (rows,) = keep.shape
    _, _, H, a, b = state.shape
    order = jnp.argsort(keep, stable=True).astype(jnp.int32)  # the live rows, then the kept
    live = rows - jnp.sum(keep, dtype=jnp.int32)
    at = jnp.where(
        (jnp.arange(rows) < live) | (live == 0), order, order[jnp.maximum(live - 1, 0)]
    )
    block = pl.BlockSpec((None, None, G, a, b), lambda j, t, order, at, live, layer: (layer[0], at[t], j, 0, 0))
    return pl.pallas_call(
        _rows(tile, len(operands)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(H // G, rows),
            in_specs=[*specs, block],
            out_specs=[_a_head(G, out_width), block],
            scratch_shapes=scratch,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows, H, out_width), _F32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        input_output_aliases={4 + len(operands): 1},
        # Rows run in order: a kept row's block is the last live row's.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=name,
    )(order, at, live[None], layer[None], *operands, state)


def _a_head(G, width):
    """The block of an operand ``[rows, H, width]``: a row's group of ``G`` heads."""
    return pl.BlockSpec((None, G, width), lambda j, t, order, at, live, layer: (order[t], j, 0))


def _held(step, S: Rows, *operands):
    """``step`` (one of the two below, a trace a shape and not a layer) on
    the rows ``S`` names: ``(out, Rows)`` with the state after the step."""
    keep = jnp.zeros((S.rows,), bool) if S.keep is None else S.keep
    out, state = step(S.state, jnp.asarray(S.layer, jnp.int32), keep, *operands, interpret=S.interpret)
    return out, dataclasses.replace(S, state=state)


@functools.partial(jax.jit, static_argnames="interpret")
def _kda_rows(state, layer, keep, q, k, v, g, beta, *, interpret):
    q, k, v, g = (x.astype(_F32) for x in (q, k, v, g))
    beta = beta.astype(_F32)[..., None]
    H, dk, dv = state.shape[2:]
    G = head_group(H, dk, dv)
    return _call(
        _kda_tile, "state_step_kda", state, layer, keep, G, (q, k, beta * k, g, beta * v),
        [_a_head(G, w) for w in (dk, dk, dk, dk, dv)], dv, (), interpret,
    )


@functools.partial(jax.jit, static_argnames="interpret")
def _ssd_rows(state, layer, keep, x, dt, A, B, C, D, *, interpret):
    x, dt, A, B, C, D = (a.astype(_F32) for a in (x, dt, A, B, C, D))
    H, P = x.shape[-2:]
    groups, N = B.shape[-2:]
    decay = jnp.broadcast_to(jnp.exp(dt * A)[..., None], x.shape)
    G = head_group(H, P, N)
    a_row = pl.BlockSpec((None, groups, N), lambda j, t, order, at, live, layer: (order[t], 0, 0))
    y, state = _call(
        functools.partial(_ssd_tile, heads_a_group=H // groups), "state_step_ssd", state, layer, keep, G,
        (x * dt[..., None], decay, B, C), [_a_head(G, P), _a_head(G, P), a_row, a_row], P,
        [pltpu.VMEM((P, -(-G // _LANES) * _LANES), _F32)], interpret,
    )
    return y + D[:, None] * x, state


def kda(q, k, v, g, beta, S):
    """:func:`ray_tpu.ops.delta_rule.kda_step` (``q, k, g`` [rows, H, d_k],
    ``v`` [rows, H, d_v], ``beta`` [rows, H]), and for ``S`` a :class:`Rows`
    the same step taken by the kernel where the state lies: ``(o [rows, H,
    d_v] float32, Rows)``."""
    if not isinstance(S, Rows):
        return kda_step(q, k, v, g, beta, S)
    return _held(_kda_rows, S, q, k, v, g, beta)


def ssd(x, dt, A, B, C, D, h):
    """:func:`ray_tpu.ops.ssd.ssd_step` (``x`` [rows, H, P], ``dt`` [rows,
    H], ``A``, ``D`` [H], ``B``, ``C`` [rows, groups, N]), and for ``h`` a
    :class:`Rows` the same step taken by the kernel where the state lies:
    ``(y [rows, H, P] float32, Rows)``."""
    if not isinstance(h, Rows):
        return ssd_step(x, dt, A, B, C, D, h)
    return _held(_ssd_rows, h, x, dt, A, B, C, D)
