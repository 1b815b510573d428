"""Ring attention: causal attention with the sequence sharded over a mesh
axis, K/V chunks rotating around the ring via ppermute.

SURVEY §5.7: the reference has NO sequence/context parallelism of its own
(grep finds only vLLM config passthrough) — this is TPU-native sequence
scaling: each `sp` rank holds S/sp of Q/K/V; at step t it computes blockwise
attention of its local Q against the K/V chunk that originated at rank
(idx - t) mod sp, merges with an online softmax, and passes the chunk to its
right neighbor. Collectives are compiled ppermutes riding ICI; activation
memory per chip is O(S/sp * S/sp) scores instead of O(S^2).

Causality at chunk granularity falls out of global position ids: fully
future chunks mask to -inf and contribute nothing (the classic simple ring;
a skip-ahead schedule would halve the flops).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh,
    axis: str = "sp",
    scale: float | None = None,
) -> jax.Array:
    """Causal attention over [B, H, S, D] with S sharded over ``axis``.

    Other mesh axes (batch over dp/fsdp, heads over tp) stay under the
    compiler's automatic SPMD — only ``axis`` is manual here.
    """
    B, H, S, D = q.shape
    sp = mesh.shape[axis]
    if S % sp:
        raise ValueError(f"seq len {S} not divisible by {axis} size {sp}")
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    s_local = S // sp

    def local(q_l, k_l, v_l):
        idx = jax.lax.axis_index(axis)
        rows = idx * s_local + jnp.arange(s_local)  # global q positions
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        qf = q_l.astype(jnp.float32) * scale

        def step(carry, t):
            acc, m, l, k_cur, v_cur = carry
            src = (idx - t) % sp  # which global chunk k_cur/v_cur hold
            cols = src * s_local + jnp.arange(s_local)
            s = jnp.einsum(
                "bhqd,bhkd->bhqk", qf, k_cur.astype(jnp.float32)
            )
            mask = rows[:, None] >= cols[None, :]
            s = jnp.where(mask[None, None], s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32)
            )
            k_next = jax.lax.ppermute(k_cur, axis, perm)
            v_next = jax.lax.ppermute(v_cur, axis, perm)
            return (acc_new, m_new, l_new, k_next, v_next), None

        shape = q_l.shape[:3]
        # Fresh zero/neg-inf constants are device-invariant; the scan carry
        # becomes sp-varying after the first step — mark them up front.
        acc0, m0, l0 = jax.tree.map(
            lambda z: jax.lax.pcast(z, (axis,), to="varying"),
            (
                jnp.zeros(q_l.shape, jnp.float32),
                jnp.full(shape, _NEG_INF, jnp.float32),
                jnp.zeros(shape, jnp.float32),
            ),
        )
        init = (acc0, m0, l0, k_l, v_l)
        (acc, _m, l, _k, _v), _ = jax.lax.scan(
            step, init, jnp.arange(sp)
        )
        return (acc / l[..., None]).astype(q_l.dtype)

    seq_spec = P(None, None, axis, None)
    return jax.shard_map(  # raylint: disable=RL102 -- constructed under the enclosing jit trace of the attention caller; rebuilt once per outer trace, not per step
        local,
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec),
        out_specs=seq_spec,
        axis_names={axis},
    )(q, k, v)
