"""One stretch of keys folded into a running softmax under a mask of selected
positions, the scores never leaving the chip.

A prefill chunk of a model with an indexer attends, a query, the positions
its indexer kept: no stretch of the table can be skipped (2,048 queries'
choices cover it) and no row can be fetched by index at the chip's speed, so
attention runs over expanded keys and values under the selection's mask, a
stretch of the table at a time (``models/deepseek_v32.py:attend_selected``).
In XLA's own operations a step of that fold writes the float32 scores ``[H,
queries, stretch]`` to HBM, reads them back for the maximum, computes them
once more for the sum and once more for the weighted values: at 128 heads
2.7 GB a step where the products need 0.3 ms (PERF.md section 6, PR 56:
122 ms a layer a chunk at 16k of context, six times the products' time).

:func:`fold_step` is that step as one Pallas call: a grid of (heads in
groups of ``_HEADS``, queries in tiles of ``_QUERIES``); a cell holds its
queries, the stretch's keys and values of its heads, the mask's tile as an
additive bias, and its rows of the carry (maximum and sum in two lanes of one
array, weighted values in another);
scores, exponentials and the two products stay in VMEM; the carry goes back
where it came from (aliased). The same arithmetic as the fold: bfloat16
operands, float32 scores and softmax, the weights in the values' dtype.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
# Heads and queries a grid cell: at a stretch of 1,024 keys of 192 (in 256
# lanes) and values of 128 a cell holds 0.5 MB of queries, 3 MB of keys and
# values, a bias tile of 0.5 MB and 1 MB of carry in and out, twice for the
# pipeline, and 1 MB of float32 scores.
_HEADS = 4
_QUERIES = 256
_VMEM_LIMIT_BYTES = 64 * 2**20
NAME = "selected_attention_fold"


def fits(heads: int, queries: int, keys: int, value_width: int, dtype, mesh=None) -> bool:
    """Whether :func:`fold_step` takes these shapes on a TPU: heads and
    queries in whole cells, keys and values in whole lane tiles, two-byte
    operands, no mesh over chips (the compiler cannot partition a Mosaic
    call)."""
    return (
        (mesh is None or mesh.size == 1)
        and heads % _HEADS == 0 and queries % _QUERIES == 0
        and keys % 128 == 0 and value_width % 128 == 0
        and jnp.dtype(dtype).itemsize == 2
    )


def _kernel(q_ref, k_ref, v_ref, bias_ref, ml_ref, acc_ref, ml_out, acc_out, *, scale, heads):
    bias = bias_ref[...].astype(_F32)  # [queries, keys]: 0 where kept, -1e30 elsewhere
    lane = jax.lax.broadcasted_iota(jnp.int32, ml_ref.shape[1:], 1)
    for g in range(heads):
        s = jax.lax.dot_general(
            q_ref[g], k_ref[g], (((1,), (1,)), ((), ())), preferred_element_type=_F32
        ) * scale + bias
        ml = ml_ref[g]
        m_prev, l_prev = ml[:, 0:1], ml[:, 1:2]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alive = jnp.exp(m_prev - m_new)
        e = jnp.exp(s - m_new)
        l_new = alive * l_prev + jnp.sum(e, axis=1, keepdims=True)
        ml_out[g] = jnp.where(lane == 0, m_new, jnp.where(lane == 1, l_new, 0.0))
        acc_out[g] = alive * acc_ref[g] + jax.lax.dot_general(
            e.astype(v_ref.dtype), v_ref[g], (((1,), (0,)), ((), ())), preferred_element_type=_F32
        )


def carry(heads: int, queries: int, value_width: int):
    """A running softmax that has seen nothing: ``(ml [H, T, 128], acc [H, T,
    Dv])`` float32. A row's maximum and sum ride in lanes 0 and 1 of ``ml``
    (a ``[H, T, 1]`` array a piece is laid out in whole lane tiles all the
    same: two arrays of 134 MB at 128 heads of 2,048 queries, read and
    written a call, where this is one)."""
    ml = jnp.zeros((heads, queries, 128), _F32).at[:, :, 0].set(-1e30)
    return ml, jnp.zeros((heads, queries, value_width), _F32)


def result(carried):
    """The weighted values over the sum, [H, T, Dv] float32."""
    ml, acc = carried
    return acc / ml[:, :, 1:2]


def fold_step(q, k, v, keep, carried, *, scale: float, interpret: bool = False):
    """``carried`` (:func:`carry`) with the stretch folded in: ``q`` [H, T,
    Dk] against ``k`` [H, S, Dk] and ``v`` [H, S, Dv] under ``keep`` [T, S]
    bool (``Dk`` as it comes: a block as wide as the array is the compiler's
    to lay out, and padding 192 to 256 here cost a copy of the queries a
    stretch). A row that keeps nothing of the stretch while its maximum is
    still -1e30 gathers ``exp(0)`` a column, which the first stretch that
    holds one of its positions multiplies away, as in the fold."""
    H, T, Dk = q.shape
    S, Dv = k.shape[1], v.shape[2]
    G, tq = math.gcd(H, _HEADS), math.gcd(T, _QUERIES)
    bias = jnp.where(keep, 0.0, -1e30).astype(jnp.bfloat16)
    rows = lambda width: pl.BlockSpec((G, tq, width), lambda h, i: (h, i, 0))  # noqa: E731
    whole = lambda width: pl.BlockSpec((G, S, width), lambda h, i: (h, 0, 0))  # noqa: E731
    ml, acc = carried
    return tuple(pl.pallas_call(
        functools.partial(_kernel, scale=scale, heads=G),
        grid=(H // G, T // tq),
        in_specs=[
            rows(Dk), whole(Dk), whole(Dv), pl.BlockSpec((tq, S), lambda h, i: (i, 0)),
            rows(ml.shape[2]), rows(Dv),
        ],
        out_specs=[rows(ml.shape[2]), rows(Dv)],
        out_shape=[jax.ShapeDtypeStruct(a.shape, _F32) for a in (ml, acc)],
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        name=NAME,
    )(q, k, v, bias, ml, acc))
