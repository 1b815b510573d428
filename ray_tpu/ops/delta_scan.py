"""The delta rule's chunked prefill with the state and a chunk's values held
on the chip: :func:`ray_tpu.ops.delta_rule.kda_chunked`'s mathematics as one
Pallas (Mosaic) call a layer.

The plain form is a ``lax.scan`` whose every value the size of ``k`` goes out
to the device's memory and comes back between XLA's fusions, over operands
re-laid ``[chunks, H, CHUNK, d]``. Here the grid is ``(head groups,
chunks)``, the chunk axis sequential: a group's state ``[G, d_k, d_v]``
float32 lives in VMEM scratch from its first chunk to its last (read from
``S0`` once, written to ``S_T`` once), and ``q, k, v, g`` come in as the
``[T, H d]`` views the projection left, a block ``(CHUNK, G d)``; ``o`` goes
out ``[T, H d_v]`` the same way. Nothing is transposed in HBM on either side.
``beta``, a number a head a position, is laid ``[groups, T, G]`` by the
caller's fusion, so that a block of it is whole in its last axis.

A grid step, chunk ``c`` of a group's heads (:func:`_kernel`):

1. A head at a time: ``Gc``, the running sum of ``g`` down the chunk
   (log-step shifts), and ``[beta k e^Gc; q e^Gc] . S`` in one product: the
   right-hand side ``R = beta v - ...`` of the unit lower-triangular system
   ``(I + A) u = R`` and the state's part of ``o``.
2. By blocks of :data:`BLOCK` rows, first to last, a loop of the program.
   The columns before the block through the matrix unit, ``exp(Gc_t - Gc_i)
   = exp(Gc_t - Gc_r) exp(Gc_r - Gc_i)`` at the block's first row ``r`` (both
   exponents at or below zero): ``pair = [beta k e^(Gc - Gc_r); q e^(Gc -
   Gc_r)] . (k e^(Gc_r - Gc_i))^T`` against the chunk's every column, those
   at or past ``r`` as zeros, then ``pair . R``, whose rows before ``r`` hold
   ``u`` by then, off the block's ``R`` and onto its ``o``. The block's own
   columns one at a time, element-wise: column ``i`` is ``k_i exp(min(Gc_t -
   Gc_i, 0))`` against the block's rows of ``beta k`` and ``q``, summed over
   the channel; row ``i`` of ``R`` is final by then (only columns before
   ``i`` reach it), so the column is applied at once, ``R[t] -= A[t, i] u_i``
   for ``t > i`` and ``o[t] += qk[t, i] u_i`` for ``t >= i``. That is forward
   substitution, a column at a time over the chunk's 64 rows: ``(I + A)^-1
   R`` exactly for any unit lower-triangular ``A``, with no inverse formed,
   no series, and no ``[C, C, d_k]`` decay. Inside the loop's body the heads
   are the innermost loop of the text, so that one head's products and
   exponentials fill the slots another's dependent steps leave empty.
3. A head at a time: ``S <- e^(Gc_C) S + (k e^(Gc_C - Gc))^T u``, one
   transposition (the decay's column rides in it) and one product.

Float32 throughout, every product at ``Precision.HIGHEST``, every exponent at
or below zero: what the plain form computes, in another order of additions.

Heads a grid step (:func:`head_group`, :data:`_GROUP`), from what the v5e
read at ``[2048, 64, 128]`` (``tools/delta_rule_chip.py``; PERF.md section 6,
PR 54): 4 heads 2.43 ms, 8 heads 2.13, 16 heads 2.01, where one head a step
would pay 0.7 ms in its 2,048 grid steps alone and leave a chain of sixty
dependent column steps with nothing beside it. The text of the body grows
with the heads (a block's columns are unrolled for each), and it is traced and
lowered again at every start of every program that holds the call: the tool's
first call took 1.5-2.0 s at 4 heads, 2.1-2.3 at 8 and 3.6-4.1 at 16, and a
replica's start about twice that a program. So 4: a chunk of Solar's pays
0.9 ms of its 45 for two seconds of every start. VMEM: 0.8 MB a head (five
blocks twice over, the state's two twice over and once in scratch, six
chunks of scratch), 3.3 MB of the 32 asked for.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.delta_rule import BLOCK, CHUNK, kda_chunked

_F32 = jnp.float32
_PREC = jax.lax.Precision.HIGHEST
_LANES, _SUBLANES = 128, 8
_GROUP = 4  # heads a grid step, where the heads divide by it: the docstring
# The rows from which a prefill program scans through the kernel. Its body is
# traced and lowered again at every start of every program that holds it,
# whatever the rows: at 8 heads a grid step the v5e's host took 4 s a program
# (PERF.md section 6, PR 54: Kimi Linear's replica with the kernel in its 1,024
# and 2,048 buckets came up in 67.9 s where the parent took 58.3; Solar's one
# chunk program hides most of it behind the weights' draw). So the largest
# bucket alone, which gets the most back (13 ms a Kimi Linear prefill of 2,048
# rows, 7 of 1,024, 2 of 512). The same reason as
# ``ops.paged_prefill_attention._MIN_TOKENS``.
_MIN_TOKENS = 2048
# What the call may take of a core's VMEM (128 MiB on a v5e; the compiler's
# default scope is 16).
_VMEM_LIMIT_BYTES = 32 * 2**20


@dataclasses.dataclass(frozen=True)
class Held:
    """A prefill's starting state ``[H, d_k, d_v]``, to be scanned from by
    the kernel: what :func:`ray_tpu.models.paged.state_prefill` hands a
    family's mixer in place of the array where the kernel runs. :func:`kda`
    given one scans through :func:`kda_scan` and returns the state an array."""

    state: jax.Array
    interpret: bool = False


def head_group(heads: int) -> int:
    """Heads a grid step: the most that divide ``heads``, up to
    :data:`_GROUP`."""
    return next(g for g in range(min(_GROUP, heads), 0, -1) if heads % g == 0)


def tiles(tokens: int, heads: int, d_k: int, d_v: int) -> bool:
    """Whether a prefill of ``tokens`` rows over heads of ``[d_k, d_v]`` has
    the kernel's shapes: a head's columns of ``q, k, v, g`` one whole lane
    tile each (a block is ``(CHUNK, G 128)``, and the state's update turns one
    ``[128, 128]`` tile), whatever the heads' count (:func:`head_group` finds a
    group for any), and :data:`_MIN_TOKENS` rows or more."""
    return tokens >= _MIN_TOKENS and d_k == _LANES and d_v == _LANES


def fits(tokens: int, heads: int, d_k: int, d_v: int, mesh=None) -> bool:
    """Whether a prefill program of ``tokens`` rows built in this process
    scans such heads through the kernel: on a TPU, outside a mesh of more
    than one chip (the compiler cannot partition a Mosaic call), at shapes
    that :func:`tiles`. Decided by what the code can see, like
    ``ops.state_step.fits``; nothing a user sets reaches it."""
    return (
        jax.default_backend() == "tpu"
        and (mesh is None or mesh.size == 1)
        and tiles(tokens, heads, d_k, d_v)
    )


def _dot(a, b):
    return jnp.dot(a, b, precision=_PREC, preferred_element_type=_F32)


def _dot_nt(a, b):  # a . b^T
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=_PREC, preferred_element_type=_F32
    )


def _running_sum(g):
    """``g`` [C, d] summed down the rows, by log-step shifts: row ``t`` takes
    row ``t - s`` for ``s`` = 1, 2, 4, ..."""
    C = g.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    s = 1
    while s < C:
        g = g + jnp.where(row >= s, pltpu.roll(g, s, 0), 0.0)
        s *= 2
    return g


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref, st_ref, S, Gc, K, KB, Q, R, O, *, G):
    """A grid step: chunk ``c`` of head group ``j``. ``q, k, v, g, o`` [C, G
    d]; ``beta`` [C, G]; ``s0, st`` and the scratch ``S`` [G, d_k, d_v];
    scratch ``Gc, K, KB, Q, R, O`` [G, C, d]: the running sum, ``k``, ``beta
    k``, ``q``, the system's right-hand side, which becomes ``u`` a row at a
    time, and ``o``: a head a leading index, so that a block's rows are read
    at the offset the loop over blocks gives."""
    c = pl.program_id(1)
    C, block = CHUNK, BLOCK
    d = S.shape[1]
    head = lambda h: slice(h * d, (h + 1) * d)  # noqa: E731

    @pl.when(c == 0)
    def _first():
        S[...] = s0_ref[...]

    for h in range(G):
        q, k = q_ref[:, head(h)], k_ref[:, head(h)]
        beta = beta_ref[:, h : h + 1]  # [C, 1]
        G_h = _running_sum(g_ref[:, head(h)])
        eG = jnp.exp(G_h)
        kb = beta * k
        read = _dot(jnp.concatenate([kb * eG, q * eG], axis=0), S[h])  # [2 C, d_v]
        Gc[h], K[h], KB[h], Q[h] = G_h, k, kb, q
        R[h] = beta * v_ref[:, head(h)] - read[:C]
        O[h] = read[C:]

    tile, n_tiles = _SUBLANES, block // _SUBLANES  # a block's rows by sublane tiles
    col = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    at = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    by_tile = lambda x: [x[i * tile : (i + 1) * tile] for i in range(n_tiles)]  # noqa: E731

    def a_block(b, _):
        r = pl.multiple_of(b * block, block)
        rows = pl.ds(r, block)
        Gb, Kb, KBb, Qb, Rb, Ob = ([None] * G for _ in range(6))
        for h in range(G):
            # The columns before the block, through the matrix unit: the
            # chunk's every column, those at or past ``r`` as zeros. (The
            # first block has none, and takes the products all the same: set
            # apart under a condition they cost every block 0.36 ms a layer
            # more than they save, the heads' work no longer one stretch.)
            g, kb, q = Gc[h, rows], KB[h, rows], Q[h, rows]
            from_r = jnp.exp(g - g[:1])
            to_r = jnp.where(col < r, jnp.exp(jnp.minimum(g[:1] - Gc[h], 0.0)), 0.0)
            pair = _dot_nt(jnp.concatenate([kb * from_r, q * from_r], axis=0), K[h] * to_r)  # [2 block, C]
            through = _dot(pair, R[h])  # the rows before r hold u by now
            Gb[h], Kb[h], KBb[h], Qb[h] = by_tile(g), by_tile(K[h, rows]), by_tile(kb), by_tile(q)
            Rb[h] = by_tile(R[h, rows] - through[:block])
            Ob[h] = by_tile(O[h, rows] + through[block:])
        # The block's own columns, a sublane tile of rows at a time: the tiles
        # above a column's hold no row under it and drop out of its step, the
        # tiles below it need no mask (and no clamp: the sum only falls).
        for j in range(block):
            t, jj = divmod(j, tile)
            under, from_j = (at > jj).astype(_F32), (at >= jj).astype(_F32)
            for h in range(G):
                g_j, k_j, u = (x[h][t][jj : jj + 1] for x in (Gb, Kb, Rb))
                for i in range(t, n_tiles):
                    fall = Gb[h][i] - g_j
                    kd = jnp.exp(jnp.minimum(fall, 0.0) if i == t else fall) * k_j
                    kk = jnp.sum(KBb[h][i] * kd, axis=-1, keepdims=True)  # A[t, j], beta folded in
                    qk = jnp.sum(Qb[h][i] * kd, axis=-1, keepdims=True)
                    if i == t:
                        kk, qk = kk * under, qk * from_j
                    Rb[h][i] = Rb[h][i] - kk * u
                    Ob[h][i] = Ob[h][i] + qk * u
        for h in range(G):
            R[h, rows] = jnp.concatenate(Rb[h], axis=0)
            O[h, rows] = jnp.concatenate(Ob[h], axis=0)
        return _

    jax.lax.fori_loop(0, C // block, a_block, 0)

    # The stack that is turned: the chunk's rows, a sublane tile of the decay,
    # zeros up to whole lane tiles of rows (one aligned transposition).
    tall = -(-(C + tile) // _LANES) * _LANES
    for h in range(G):
        o_ref[:, head(h)] = O[h]
        G_h = Gc[h]
        last = G_h[C - 1 : C]
        turned = jnp.concatenate(
            [
                K[h] * jnp.exp(last - G_h),
                jnp.broadcast_to(jnp.exp(last), (tile, d)),
                jnp.zeros((tall - C - tile, d), _F32),
            ],
            axis=0,
        ).T  # [d_k, C | 8 | ...]: (k e^(Gc_C - Gc))^T, then the decay's column
        u = jnp.concatenate([R[h], jnp.zeros((tall - C, R.shape[2]), _F32)], axis=0)
        S[h] = turned[:, C : C + 1] * S[h] + _dot(turned, u)

    @pl.when(c == pl.num_programs(1) - 1)
    def _last():
        st_ref[...] = S[...]


@functools.partial(jax.jit, static_argnames=("interpret", "group"))
def _scan(q, k, v, g, beta, S0, *, interpret, group=None):
    """``q, k, v, g`` [T, H d] float32, ``T`` in whole chunks; ``beta`` [T,
    H]; ``S0`` [H, d_k, d_v]. Jitted, so that a program's layers are one
    trace of the kernel."""
    T = q.shape[0]
    H, d, _ = S0.shape
    G = group or head_group(H)
    wide = pl.BlockSpec((CHUNK, G * d), lambda j, c: (c, j))
    state = pl.BlockSpec((G, d, d), lambda j, c: (j, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, G=G),
        grid=(H // G, T // CHUNK),
        in_specs=[wide, wide, wide, wide, pl.BlockSpec((None, CHUNK, G), lambda j, c: (j, c, 0)), state],
        out_specs=[wide, state],
        out_shape=[
            jax.ShapeDtypeStruct((T, H * d), _F32),
            jax.ShapeDtypeStruct((H, d, d), _F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, d, d), _F32),
            *[pltpu.VMEM((G, CHUNK, d), _F32)] * 6,
        ],
        # Chunks run in order: the state in scratch is the chunk before's.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="kda_scan",
    )(q, k, v, g, beta.reshape(T, H // G, G).transpose(1, 0, 2), S0)


def kda_scan(q, k, v, g, beta, S0, *, interpret: bool = False, group=None):
    """:func:`ray_tpu.ops.delta_rule.kda_chunked`, operands and results alike
    (``q, k, g`` [T, H, d_k], ``v`` [T, H, d_v], ``beta`` [T, H], ``S0`` [H,
    d_k, d_v]; ``(o [T, H, d_v] float32, S_T float32)``), through the kernel,
    for ``d_k == d_v``. ``interpret``: in the Pallas interpreter, whatever
    the platform and the width; ``group``: the heads a grid step, for the
    chip tool's comparison."""
    T, H, d = v.shape
    pad = -T % CHUNK
    flat = lambda a: jnp.pad(  # noqa: E731
        a.astype(_F32).reshape(T, -1), [(0, pad), (0, 0)]
    )  # a padded position leaves the state alone: g = 0, beta = 0
    o, S = _scan(
        *(flat(a) for a in (q, k, v, g, beta)), S0.astype(_F32), interpret=interpret, group=group
    )
    return o[:T].reshape(T, H, d), S


def kda(q, k, v, g, beta, S0):
    """:func:`ray_tpu.ops.delta_rule.kda_chunked`, and for ``S0`` a
    :class:`Held` the same scan through the kernel: ``(o, S_T)`` alike."""
    if not isinstance(S0, Held):
        return kda_chunked(q, k, v, g, beta, S0)
    return kda_scan(q, k, v, g, beta, S0.state, interpret=S0.interpret)
