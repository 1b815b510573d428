"""Causal self-attention: Pallas flash kernels on TPU, jnp reference elsewhere.

Flash attention keeps the O(S^2) score matrix out of HBM: each q-block streams
k/v-blocks through VMEM with a running (max, denominator, accumulator) online
softmax, so the MXU sees back-to-back [block_q, d] x [d, block_k] matmuls and
HBM traffic stays O(S·d). The reference framework has no attention kernel of
its own (it orchestrates engines that bring their own; SURVEY.md §5.7) — this
is part of the TPU-native compute tier that replaces those engines.

Both directions are fused:

- forward: online-softmax kernel that also writes the per-row logsumexp (LSE).
- backward: ONE fused kernel sweeping k-blocks that recomputes block-local
  probabilities from the saved LSE (p = exp(s - lse)) instead of re-running
  the softmax, producing dk/dv per block and accumulating dq in a VMEM
  scratch. Nothing O(S^2) ever touches HBM.

Matmuls run on the MXU in the input dtype (bf16 by design) with float32
accumulation (preferred_element_type); softmax statistics stay float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30
# Mesh axes (ray_tpu.parallel.mesh.AXIS_NAMES) the [B, H, S, D] operands are
# sharded over: batch over the data axes, heads over tensor parallel.
_BATCH_AXES = ("dp", "fsdp")
_HEAD_AXIS = "tp"


def _masked_scores(q, k, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    S_q, S_k = q.shape[2], k.shape[2]
    mask = jnp.tril(jnp.ones((S_q, S_k), dtype=bool))
    return jnp.where(mask[None, None], s, _NEG_INF)


def _reference_causal_attention(q, k, v, scale):
    # q,k,v: [B, H, S, D]
    p = jax.nn.softmax(_masked_scores(q, k, scale), axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _dot(a, b, trans_b=False):
    """MXU matmul in the operand dtype with f32 accumulation."""
    dims = (((1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


_LOG2E = 1.4426950408889634


def _scaled(q_ref, scale):
    """Load a q block pre-scaled by scale*log2(e) (exp2 online softmax).

    Folding the scale into the small [block_q, d] operand removes a full
    [block_q, block_k] multiply pass from every inner iteration, and exp2 is
    cheaper than exp on the VPU.
    """
    q = q_ref[0, 0]
    return (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_q, block_k
):
    # Block shapes: q_ref/o_ref [1, 1, block_q, d]; k_ref/v_ref [1, 1, S, d];
    # lse_ref [1, 1, block_q, 1] (trailing unit dim satisfies TPU tiling).
    # lse is stored in base-2 units, matching the exp2 softmax.
    qi = pl.program_id(2)
    qs = _scaled(q_ref, scale)
    d = qs.shape[-1]

    q_start = qi * block_q
    # Interior k-blocks are entirely below the diagonal (no masking needed);
    # the remaining blocks straddle it and pay for the mask. VPU work on the
    # [block_q, block_k] tile dominates this kernel, so the interior loop
    # carrying ~3 fewer elementwise passes is the difference between ~10% and
    # ~2x that MXU utilisation.
    n_interior = (q_start + 1) // block_k
    n_total = (q_start + block_q + block_k - 1) // block_k

    row_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(j, carry, masked):
        acc, m, l = carry
        k_blk = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = _dot(qs, k_blk, trans_b=True)  # [block_q, block_k] f32, base-2
        if masked:
            col_ids = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(row_ids >= col_ids, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + _dot(p.astype(v_blk.dtype), v_blk)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    carry = jax.lax.fori_loop(
        0, n_interior, functools.partial(body, masked=False), (acc0, m0, l0)
    )
    acc, m, l = jax.lax.fori_loop(
        n_interior, n_total, functools.partial(body, masked=True), carry
    )
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log2(l)


def _per_shard(fn, mesh):
    """``fn`` over [B, H, S, ·] operands, run on each device's shard.

    XLA cannot partition a Mosaic call ("Mosaic kernels cannot be
    automatically partitioned"), so under a mesh the kernels run inside a
    shard_map: batch over the data axes, heads over ``tp``. Mesh axes the
    spec does not name see replicated operands and repeat the work, which
    is what automatic partitioning does with them too."""
    if mesh is None or mesh.size == 1:
        return fn
    batch = tuple(a for a in _BATCH_AXES if a in mesh.shape)
    spec = P(batch or None, _HEAD_AXIS if _HEAD_AXIS in mesh.shape else None)
    return jax.shard_map(  # raylint: disable=RL102 -- built under the caller's jit trace, once per trace
        fn, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False
    )


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_q", "block_k", "interpret", "mesh"),
)
def _flash_attention_fwd_impl(
    q, k, v, scale, block_q, block_k, interpret=False, mesh=None
):
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, block_q=block_q, block_k=block_k
    )

    def shard(q, k, v):
        B, H, S, D = q.shape
        return pl.pallas_call(
            kernel,
            grid=(B, H, S // block_q),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
            ],
            interpret=interpret,
            name="flash_fwd",
        )(q, k, v)

    return _per_shard(shard, mesh)(q, k, v)


def _flash_bwd_fused_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dq_ref, dq_acc,
    *, scale, block_q, block_k, seq_len,
):
    """One-sweep backward: dk/dv for this k-block AND this k-block's
    contribution to every dq row, accumulated in a VMEM scratch that
    persists across the (sequential) k-block grid steps.

    The two-kernel backward recomputes the score matrix twice (once per
    reduction direction); the kernel is VPU-bound on exactly those
    score/prob/ds passes, so folding dq into the dk/dv sweep nearly halves
    backward time (measured ~2x fwd instead of ~3x on v5e).
    """
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    d = k.shape[-1]
    scale2 = scale * _LOG2E

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    k_start = kj * block_k
    first_q_block = k_start // block_q
    first_interior = (k_start + block_k - 1 + block_q - 1) // block_q
    num_q_blocks = seq_len // block_q
    col_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def body(i, carry, masked):
        dk_acc, dv_acc = carry
        q_blk = q_ref[0, 0, pl.ds(i * block_q, block_q), :]
        do_blk = do_ref[0, 0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q)]  # [block_q, 1]
        delta = delta_ref[0, 0, pl.ds(i * block_q, block_q)]
        qs = (q_blk.astype(jnp.float32) * scale2).astype(q_blk.dtype)
        s = _dot(qs, k, trans_b=True)  # [block_q, block_k] f32, base-2
        if masked:
            row_ids = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            s = jnp.where(row_ids >= col_ids, s, _NEG_INF)
        p = jnp.exp2(s - lse)
        pT = p.astype(do_blk.dtype)
        dv_new = dv_acc + jax.lax.dot_general(
            pT, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = _dot(do_blk, v, trans_b=True)
        ds = p * (dp - delta)
        ds_lp = ds.astype(q_blk.dtype)
        dk_new = dk_acc + jax.lax.dot_general(
            ds_lp, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_acc[pl.ds(i * block_q, block_q), :] += _dot(ds_lp, k)
        return dk_new, dv_new

    zeros = jnp.zeros((block_k, d), jnp.float32)
    carry = jax.lax.fori_loop(
        first_q_block,
        jnp.minimum(first_interior, num_q_blocks),
        functools.partial(body, masked=True),
        (zeros, zeros),
    )
    dk_acc, dv_acc = jax.lax.fori_loop(
        first_interior, num_q_blocks, functools.partial(body, masked=False), carry
    )
    dk_ref[0, 0] = (dk_acc * scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv_acc.astype(dv_ref.dtype)

    @pl.when(kj == n_k - 1)
    def _flush():
        dq_ref[0, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_q", "block_k", "interpret", "mesh"),
)
def _flash_attention_bwd_impl(
    q, k, v, o, lse, g, scale, block_q, block_k, interpret=False, mesh=None
):
    def shard(q, k, v, o, lse, g):
        B, H, S, D = q.shape
        # delta_i = rowsum(dO_i * O_i): cheap elementwise+reduce, XLA fuses it.
        delta = jnp.sum(
            g.astype(jnp.float32) * o.astype(jnp.float32),
            axis=-1,
            keepdims=True,
        )  # [B, H, S, 1]

        full_spec = pl.BlockSpec((1, 1, S, D), lambda b, h, j: (b, h, 0, 0))
        fullrow_spec = pl.BlockSpec((1, 1, S, 1), lambda b, h, j: (b, h, 0, 0))
        kd_spec = pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0))
        dk, dv, dq = pl.pallas_call(
            functools.partial(
                _flash_bwd_fused_kernel,
                scale=scale,
                block_q=block_q,
                block_k=block_k,
                seq_len=S,
            ),
            grid=(B, H, S // block_k),
            in_specs=[
                full_spec, kd_spec, kd_spec, full_spec, fullrow_spec,
                fullrow_spec,
            ],
            out_specs=[kd_spec, kd_spec, full_spec],
            out_shape=[
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
                jax.ShapeDtypeStruct(q.shape, q.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((S, D), jnp.float32)],
            interpret=interpret,
            name="flash_bwd",
        )(q, k, v, g, lse, delta)
        return dq, dk, dv

    return _per_shard(shard, mesh)(q, k, v, o, lse, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, scale, block_q, block_k, interpret=False, mesh=None):
    o, _ = _flash_attention_fwd_impl(
        q, k, v, scale, block_q, block_k, interpret, mesh
    )
    return o


def _flash_fwd(q, k, v, scale, block_q, block_k, interpret=False, mesh=None):
    o, lse = _flash_attention_fwd_impl(
        q, k, v, scale, block_q, block_k, interpret, mesh
    )
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, block_q, block_k, interpret, mesh, res, g):
    q, k, v, o, lse = res
    return _flash_attention_bwd_impl(
        q, k, v, o, lse, g, scale, block_q, block_k, interpret, mesh
    )


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def _platform(mesh) -> str:
    """The platform the attention is being built for: the mesh's devices
    when a mesh is in scope (which also holds when compiling ahead of time
    for a topology this process has no devices of), else the default
    backend."""
    if mesh is not None:
        return mesh.devices.flat[0].platform
    return jax.default_backend()


def uses_flash_kernel(
    seq: int,
    *,
    impl: str = "auto",
    block_q: int = 256,
    block_k: int = 256,
    mesh=None,
) -> bool:
    """Whether causal_attention with these settings dispatches to the Pallas
    kernel (used by model code to pick a remat policy: the flash kernel saves
    its own o/lse residuals, the jnp reference path must be checkpointed)."""
    if impl == "pallas":
        return True
    if impl != "auto":
        return False
    return (
        _platform(mesh) == "tpu"
        and seq % min(block_q, seq) == 0
        and seq % min(block_k, seq) == 0
    )


def causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    impl: str = "auto",
    scale: float | None = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
    mesh=None,
) -> jax.Array:
    """Causal attention over [batch, heads, seq, head_dim] tensors.

    impl: "auto" (pallas on TPU, reference otherwise), "pallas", "reference".
    interpret: run the pallas kernel in interpreter mode (CPU testing).
    mesh: the mesh the operands are sharded over, if any; the kernel then
    runs per shard (batch over dp/fsdp, heads over tp).
    """
    if q.ndim != 4:
        raise ValueError(f"expected [B, H, S, D], got shape {q.shape}")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if impl == "auto":
        use_pallas = uses_flash_kernel(
            q.shape[2], impl="auto", block_q=block_q, block_k=block_k,
            mesh=mesh,
        )
        impl = "pallas" if use_pallas else "reference"
    if impl == "reference":
        return _reference_causal_attention(q, k, v, scale)
    if impl != "pallas":
        raise ValueError(f"unknown attention impl {impl!r}")
    S = q.shape[2]
    bq = min(block_q, S)
    bk = min(block_k, S)
    if S % bq or S % bk:
        raise ValueError(
            f"impl='pallas' requires seq len divisible by block sizes; got "
            f"S={S}, block_q={bq}, block_k={bk}. Use impl='auto' to allow "
            f"fallback or pick dividing blocks."
        )
    return _flash_attention(q, k, v, scale, bq, bk, interpret, mesh)
