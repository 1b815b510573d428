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

Where the two block sizes are equal, the tile on the diagonal is not scored
whole under a mask: it is cut into groups of rows (forward; of columns,
backward) that each meet only the columns (rows) they may see, so that a
group's one masked corner is a constant of the kernel and the rest of its
scores take the interior's passes (``diag_group``; ``causal_pairs`` counts
what is scored).

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


# The most rows a group of a diagonal tile holds. A group's rows are what a
# product streams past each key tile the array has loaded, so small groups
# starve it: on a v5e at S=1,024, D=64, blocks of 1,024, forward+backward took
# 417 / 425 / 598 us a call of 50 heads in groups of 512 / 256 / 128 rows (652
# scored whole), though the smaller group scores fewer pairs (PERF.md
# section 6, PR 41).
_MAX_GROUP = 512
_LANES = 128


def diag_group(block_q: int, block_k: int) -> int | None:
    """Rows to a group when a tile on the diagonal is cut into row groups
    that each see only their own columns, or None where the tile is scored
    whole under a mask: blocks that differ (a tile then straddles the
    diagonal at an offset that moves with the grid step) or that do not
    halve into whole lane tiles. At least two groups, so that the columns
    no row of a group may see are not scored at all."""
    if block_q != block_k or block_q % (2 * _LANES):
        return None
    return min(_MAX_GROUP, block_q // 2)


def causal_pairs(
    seq: int, block_q: int, block_k: int, group: int | None
) -> tuple[int, int]:
    """(query, key) pairs the kernels score at these shapes, forward and
    backward alike, and the seq (seq + 1) / 2 that causal attention needs.
    ``group`` as ``diag_group`` gives it: None scores a straddling tile
    whole."""
    block_q, block_k = min(block_q, seq), min(block_k, seq)
    computed = 0
    for q_start in range(0, seq, block_q):
        n_interior = (q_start + 1) // block_k
        n_total = -(-(q_start + block_q) // block_k)
        if group is None:
            computed += n_total * block_q * block_k
        else:
            groups = block_q // group
            computed += n_interior * block_q * block_k
            computed += group * group * groups * (groups + 1) // 2
    return computed, seq * (seq + 1) // 2


def _lower_triangle(n):
    """bool [n, n]: row >= column."""
    return jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) >= (
        jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    )


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_q, block_k, group
):
    # Block shapes: q_ref/o_ref [1, 1, block_q, d]; k_ref/v_ref [1, 1, S, d];
    # lse_ref [1, 1, block_q, 1] (trailing unit dim satisfies TPU tiling).
    # lse is stored in base-2 units, matching the exp2 softmax.
    qi = pl.program_id(2)
    qs = _scaled(q_ref, scale)
    d = qs.shape[-1]

    q_start = qi * block_q
    # Interior k-blocks are entirely below the diagonal (no masking needed);
    # the remaining blocks straddle it. The passes over the [rows, columns]
    # f32 scores are on the vector unit (v5e has none for bf16), so a tile
    # costs its passes, and a mask built from two iotas a step adds three.
    n_interior = (q_start + 1) // block_k

    def update(carry, q, k_blk, v_blk, mask=None):
        """One running-softmax update of the rows ``q`` over the columns
        ``k_blk``; ``mask`` (bool [rows, n]) is laid over the last n."""
        acc, m, l = carry
        s = _dot(q, k_blk, trans_b=True)  # [rows, columns] f32, base-2
        if mask is not None:
            seen = s.shape[1] - mask.shape[1]
            corner = jnp.where(mask, s[:, seen:], _NEG_INF)
            s = jnp.concatenate([s[:, :seen], corner], axis=1) if seen else corner
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + _dot(p.astype(v_blk.dtype), v_blk)
        return acc_new, m_new, l_new

    def k_block(j):
        rows = pl.ds(j * block_k, block_k)
        return k_ref[0, 0, rows, :], v_ref[0, 0, rows, :]

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    carry = jax.lax.fori_loop(
        0, n_interior, lambda j, c: update(c, qs, *k_block(j)), (acc0, m0, l0)
    )
    if group is None:
        n_total = (q_start + block_q + block_k - 1) // block_k
        row_ids = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )

        def straddling(j, carry):
            col_ids = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            return update(carry, qs, *k_block(j), mask=row_ids >= col_ids)

        acc, m, l = jax.lax.fori_loop(n_interior, n_total, straddling, carry)
        o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m + jnp.log2(l)
        return
    # Equal blocks: the one tile left is the one on the diagonal. Row group r
    # scores its rows against the columns at or before its last row and no
    # others, so only its last [group, group] corner meets the mask, and it
    # makes one running-softmax update over its own width.
    k_blk, v_blk = k_block(qi)
    corner = _lower_triangle(group)
    for r in range(block_q // group):
        rows = slice(r * group, (r + 1) * group)
        seen = (r + 1) * group
        acc, m, l = update(
            tuple(x[rows] for x in carry), qs[rows], k_blk[:seen], v_blk[:seen],
            mask=corner,
        )
        o_ref[0, 0, rows, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, 0, rows, :] = m + jnp.log2(l)


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
    static_argnames=("scale", "block_q", "block_k", "group", "interpret", "mesh"),
)
def _flash_attention_fwd_impl(
    q, k, v, scale, block_q, block_k, group, interpret=False, mesh=None
):
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
        group=group,
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


# What the backward may take of a core's VMEM (128 MiB on a v5e; the compiler's
# default scope is 16): q, dO, dq and the float32 dq scratch at full length,
# and the two row statistics, whose [S, 1] float32 a lane tile pads to
# [S, 128] (two buffers each: 8 MiB of the 19 that blocks of 1,024 take at
# S=4,096).
_BWD_VMEM_LIMIT_BYTES = 48 * 2**20


def _flash_bwd_fused_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dq_ref, dq_acc,
    *, scale, block_q, block_k, group, seq_len,
):
    """One-sweep backward: dk/dv for this k-block AND this k-block's
    contribution to every dq row, accumulated in a VMEM scratch that
    persists across the (sequential) k-block grid steps.

    A backward of two kernels recomputes the score matrix twice (once per
    reduction direction); the kernel is VPU-bound on exactly those
    score/prob/ds passes, so dq rides the dk/dv sweep.
    """
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    scale2 = scale * _LOG2E

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    k_start = kj * block_k
    first_q_block = k_start // block_q
    first_interior = (k_start + block_k - 1 + block_q - 1) // block_q
    num_q_blocks = seq_len // block_q

    def sweep(rows, k, v, mask=None):
        """The query rows ``rows`` against the columns ``k``, ``v``: their
        part of those columns' dk and dv, and their part of dq added to the
        scratch; ``mask`` (bool [n, columns]) is laid over the first n rows."""
        q_blk = q_ref[0, 0, rows, :]
        do_blk = do_ref[0, 0, rows, :]
        lse = lse_ref[0, 0, rows]  # [rows, 1]
        delta = delta_ref[0, 0, rows]
        qs = (q_blk.astype(jnp.float32) * scale2).astype(q_blk.dtype)
        s = _dot(qs, k, trans_b=True)  # [rows, columns] f32, base-2
        if mask is not None:
            n = mask.shape[0]
            corner = jnp.where(mask, s[:n], _NEG_INF)
            s = jnp.concatenate([corner, s[n:]], axis=0) if n < s.shape[0] else corner
        p = jnp.exp2(s - lse)
        pT = p.astype(do_blk.dtype)
        dv = jax.lax.dot_general(
            pT, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = _dot(do_blk, v, trans_b=True)
        ds = p * (dp - delta)
        ds_lp = ds.astype(q_blk.dtype)
        dk = jax.lax.dot_general(
            ds_lp, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_acc[rows, :] += _dot(ds_lp, k)
        return dk, dv

    def q_block(i, carry, mask=None):
        dk, dv = sweep(pl.ds(i * block_q, block_q), k, v, mask)
        return carry[0] + dk, carry[1] + dv

    if group is None:
        col_ids = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )

        def straddling(i, carry):
            row_ids = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            return q_block(i, carry, mask=row_ids >= col_ids)

        zeros = jnp.zeros(k.shape, jnp.float32)
        carry = jax.lax.fori_loop(
            first_q_block,
            jnp.minimum(first_interior, num_q_blocks),
            straddling,
            (zeros, zeros),
        )
    else:
        # Equal blocks: the one straddling tile is the one on the diagonal.
        # Column group c meets the query rows at or after its first column
        # and no others, so only their first [group, group] corner meets the
        # mask; each group's dk and dv are whole after one sweep.
        corner = _lower_triangle(group)
        parts = [
            sweep(
                pl.ds(k_start + c * group, block_q - c * group),
                k[c * group:(c + 1) * group], v[c * group:(c + 1) * group],
                mask=corner,
            )
            for c in range(block_k // group)
        ]
        carry = tuple(jnp.concatenate(x, axis=0) for x in zip(*parts))
    dk_acc, dv_acc = jax.lax.fori_loop(
        first_interior, num_q_blocks, q_block, carry
    )
    dk_ref[0, 0] = (dk_acc * scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv_acc.astype(dv_ref.dtype)

    @pl.when(kj == n_k - 1)
    def _flush():
        dq_ref[0, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_q", "block_k", "group", "interpret", "mesh"),
)
def _flash_attention_bwd_impl(
    q, k, v, o, lse, g, scale, block_q, block_k, group, interpret=False, mesh=None
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
                group=group,
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
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_BWD_VMEM_LIMIT_BYTES
            ),
            interpret=interpret,
            name="flash_bwd",
        )(q, k, v, g, lse, delta)
        return dq, dk, dv

    return _per_shard(shard, mesh)(q, k, v, o, lse, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention(
    q, k, v, scale, block_q, block_k, group, interpret=False, mesh=None
):
    o, _ = _flash_attention_fwd_impl(
        q, k, v, scale, block_q, block_k, group, interpret, mesh
    )
    return o


def _flash_fwd(q, k, v, scale, block_q, block_k, group, interpret=False, mesh=None):
    o, lse = _flash_attention_fwd_impl(
        q, k, v, scale, block_q, block_k, group, interpret, mesh
    )
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, block_q, block_k, group, interpret, mesh, res, g):
    q, k, v, o, lse = res
    return _flash_attention_bwd_impl(
        q, k, v, o, lse, g, scale, block_q, block_k, group, interpret, mesh
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


def _fit_block(block: int, seq: int) -> int:
    """The block the kernels run with: ``block`` clamped to the sequence and
    halved while it does not divide it, down to two lane tiles (S=1,536 runs
    blocks of 512 where 1,024 was asked for)."""
    block = min(block, seq)
    while seq % block and block % (2 * _LANES) == 0:
        block //= 2
    return block


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
        and seq % _fit_block(block_q, seq) == 0
        and seq % _fit_block(block_k, seq) == 0
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
    bq = _fit_block(block_q, S)
    bk = _fit_block(block_k, S)
    if S % bq or S % bk:
        raise ValueError(
            f"impl='pallas' requires seq len divisible by block sizes; got "
            f"S={S}, block_q={bq}, block_k={bk}. Use impl='auto' to allow "
            f"fallback or pick dividing blocks."
        )
    return _flash_attention(
        q, k, v, scale, bq, bk, diag_group(bq, bk), interpret, mesh
    )
