"""``mla_moe``'s block with a lightning indexer in front of its attention
(DeepSeek-V3.2-Exp, ``model_type: deepseek_v32``): tenth model family of the
serving tier, and the first whose attention reads rows *chosen one by one*.

Block, layers numbered from 1, as :mod:`ray_tpu.models.mla_moe`'s: ``x +=
Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``, the first ``first_k_dense`` layers'
FFN a dense SwiGLU, the others' a routed mixture with a shared expert and a
selection bias (``router_bias``, drawn zero: :func:`init_params`). In every
layer, with ``h = RMSNorm(x)`` and ``c_q = RMSNorm(h W_dq)`` (the ``c_q`` that
MLA's queries are made from):

1. index queries ``qI = c_q W_Iq`` [T, J, d_I]; one index key a position for
   all index heads, ``kI = LayerNorm(h W_Ik)`` [T, d_I] (weight and bias);
   head weights ``w = (h W_Iw) J^-1/2 d_I^-1/2`` [T, J];
2. the first ``qk_rope_head_dim`` values of ``qI`` (every head) and of ``kI``
   are rotated by the position with MLA's YaRN frequencies, *in halves*
   (``(i, i + d_r / 2)``: MLA's shared key turns interleaved pairs);
3. ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``, float32;
4. ``S_t``: the positions of the ``min(index_topk, t + 1)`` largest ``I[t,
   :t + 1]``, a tie to the lower position;
5. MLA as ``mla_moe`` computes it, softmax and weighted sum over ``S_t`` only.

Up to ``index_topk`` positions nothing is left out and the layer is
``mla_moe``'s. The published Hadamard rotation of ``qI`` and ``kI`` (orthogonal,
on both sides of a dot product, there to spread outliers before FP8) is left
out with the FP8: index keys are kept in the activation dtype.

**The cache** is two parts under ONE block table: ``{"ckv": [L, N, block,
640], "ikv": [L, N, block, d_I]}``, the latent row of ``mla_moe`` and the
rotated index key of the same position, written together and shared by block
id together (a pooled prefix carries both).

**Prefill** (:func:`select_prefill`, :func:`attend_selected`): a chunk's
queries score the index keys of the table a stretch at a time, the positions a
query keeps are found as a *threshold* (the ``index_topk``-th largest score of
the row, by counting passes over the scores' bits: no sort), and attention
is ``latent_moe.mla_prefill``'s fold over expanded keys and values under one
more mask: on a TPU one Pallas call a layer a run of queries, which walks the
table's live stretches itself, expands a head's keys and values from the
latent rows on the chip and keeps scores and running softmax there
(:mod:`ray_tpu.ops.selected_attention`), XLA's einsums elsewhere. **Decode**
(:func:`select_decode`): a slot's query scores its table's index keys,
``lax.top_k`` names the rows, and the absorbed attention runs over those rows
gathered one by one (``latent_moe.mla_decode`` with this module's ``attend``).
Both choose exactly ``S_t``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import latent_moe, mla_moe, paged
from ray_tpu.models.common import _rms_norm, stage
from ray_tpu.models.latent_moe import ffn, final_logits, mla_decode, mla_latent, mla_query
from ray_tpu.ops import selected_attention

Params = dict
_F32 = jnp.float32

# Positions of the table that one step of the index scores takes ([T, J, 512]
# float32 is 268 MB at a chunk of 2,048 and 64 index heads) and that one step
# of the plain fold expands per head; and that the attention's kernel copies
# to the chip and folds at a time (its rows, its mask and a head's expanded
# keys and values are VMEM: PERF.md section 6, PR 57, has the sizes tried).
KEY_POSITIONS = 512
KERNEL_KEY_POSITIONS = 1024
# Queries whose scores against the whole table are held at once ([2048, 34816]
# float32 is 285 MB, and its ordered bits as much again): a prefill longer
# than this selects and attends a run of so many after another.
SELECT_QUERIES = 2048
# float32 bits mapped so that unsigned order is the floats' order: what
# minus infinity maps to.
_NEG_INF_KEY = 0x007FFFFF
# Bits of the threshold that one pass over a chunk's scores settles
# (kept_mask): at four, eight passes of fifteen counts cost half of thirty-two
# of one and as much as sixteen of three (PERF.md section 6, PR 56).
_BITS_A_PASS = 4


@dataclasses.dataclass(frozen=True)
class DeepseekV32Config(mla_moe.MlaMoeConfig):
    """``MlaMoeConfig`` and the indexer's three sizes; defaults are
    DeepSeek-V3.2-Exp's published sizes, uncut."""

    family: ClassVar[str] = "deepseek_v32"

    vocab_size: int = 129280
    n_head: int = 128
    rope_factor: float = 40.0
    first_k_dense: int = 3
    n_experts: int = 256
    experts_held: int = 256
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    index_norm_eps: float = 1e-6  # of the index key's LayerNorm

    def __post_init__(self):
        super().__post_init__()
        assert self.qk_rope_head_dim <= self.index_head_dim

    @staticmethod
    def tiny(
        n_layer: int = 3, vocab_size: int = 512, max_seq: int = 256,
        experts_held: int = 8, expert_offset: int = 0, **kw,
    ) -> "DeepseekV32Config":
        """``MlaMoeConfig.tiny`` with four index heads of 16 that keep 16
        positions: selection is at work from the 17th token."""
        base = mla_moe.MlaMoeConfig.tiny(n_layer, vocab_size, max_seq, experts_held, expert_offset)
        return DeepseekV32Config(**{
            **{f.name: getattr(base, f.name) for f in dataclasses.fields(base)},
            **dict(index_n_heads=4, index_head_dim=16, index_topk=16), **kw,
        })


# ---------------------------------------------------------------------------
# Parameters


@functools.partial(jax.jit, static_argnames="cfg")
def init_params(key: jax.Array, cfg: DeepseekV32Config) -> Params:
    """Random weights (:func:`draw_params`) with each router centred as
    :func:`ray_tpu.models.mla_moe.centre_routers` says. The checkpoint's
    balance lives in its selection bias; with seeded weights it is put in the
    router's columns, over text, as A.X-K1's is, and the bias is there and
    zero. (Balancing the bias over ids of the whole vocabulary, Kimi Linear's
    way, leaves a router tilted on the text that is served: PERF.md section 6,
    PR 33.) The centring sequences are no longer than ``index_topk`` at the
    published sizes, so the layer they go through is ``mla_moe``'s and that
    module's function serves as it stands."""
    key, sub = jax.random.split(key)
    return mla_moe.centre_routers(
        draw_params(key, cfg), sub, cfg, mla_moe._ROUTER_SEQUENCES,
        min(mla_moe._ROUTER_TOKENS, cfg.max_seq),
    )


def draw_params(key: jax.Array, cfg: DeepseekV32Config) -> Params:
    """:func:`ray_tpu.models.mla_moe.draw_params` and, a layer, the indexer's
    projections (``wi_q`` from ``c_q``, ``wi_k`` and ``wi_w`` from the normed
    hidden state; N(0, 0.02)), its LayerNorm (weight one, bias zero), and a
    zero selection bias in every expert layer."""
    pd = cfg.param_dtype
    key, sub = jax.random.split(key)
    params = mla_moe.draw_params(key, cfg)
    J, Di = cfg.index_n_heads, cfg.index_head_dim
    keys = iter(jax.random.split(sub, 3 * cfg.n_layer))

    def w(shape):
        return jax.random.normal(next(keys), shape, pd) * jnp.asarray(0.02, pd)

    layers = []
    for i, p in enumerate(params["layers"], start=1):
        p = {
            **p, "wi_q": w((cfg.q_lora_rank, J * Di)), "wi_k": w((cfg.d_model, Di)),
            "wi_w": w((cfg.d_model, J)),
            "wi_knorm": jnp.ones((Di,), pd), "wi_kbias": jnp.zeros((Di,), pd),
        }
        if cfg.is_moe(i):
            p["router_bias"] = jnp.zeros((cfg.n_experts,), _F32)
        layers.append(p)
    return {**params, "layers": layers}


# ---------------------------------------------------------------------------
# The indexer


def rotate_halves(x, cos, sin):
    """Rotate the pairs ``(i, i + d / 2)`` of the first ``d = 2 cos.shape[-1]``
    values of ``x``'s last axis (the indexer's non-interleaved convention);
    what lies behind them is left alone."""
    half = cos.shape[-1]
    x32 = x.astype(_F32)
    a, b, rest = x32[..., :half], x32[..., half : 2 * half], x32[..., 2 * half :]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, rest], axis=-1).astype(x.dtype)


@stage("attn_select")
def index_key(h, p, cfg, rope):
    """The pool's index key of each token, [..., d_I]: ``LayerNorm(h W_Ik)``
    in float32, rotated by its position *before* it is written, as a latent
    row's shared key is."""
    k = (h @ p["wi_k"].astype(cfg.dtype)).astype(_F32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + cfg.index_norm_eps)
    k = k * p["wi_knorm"].astype(_F32) + p["wi_kbias"].astype(_F32)
    return rotate_halves(k.astype(cfg.dtype), *rope)


@stage("attn_select")
def index_query(h, c_q, p, cfg, rope):
    """``(qI [..., J, d_I] rotated, w [..., J] float32)``."""
    J, Di = cfg.index_n_heads, cfg.index_head_dim
    qi = (c_q @ p["wi_q"].astype(cfg.dtype)).reshape(*c_q.shape[:-1], J, Di)
    cos, sin = (a[..., None, :] for a in rope)  # one angle for every index head
    w = jnp.dot(h, p["wi_w"].astype(cfg.dtype), preferred_element_type=_F32)
    return rotate_halves(qi, cos, sin), w * (J**-0.5 * Di**-0.5)


def _scores(qi, w, keys):
    """``sum_j w relu(qI . kI)`` of queries [..., J, d_I] against ``keys``
    [..., S, d_I] (the same leading axes), float32 [..., S]."""
    s = jnp.einsum("...jd,...sd->...js", qi, keys, preferred_element_type=_F32)
    return jnp.sum(jax.nn.relu(s) * w[..., None], axis=-2) + 0.0  # (-0.0 is 0.0: one key a value)


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(0x80000000))


def kept_mask(scores, seen, k: int):
    """``scores`` [T, S] float32 (minus infinity where ``seen`` [T, S] is
    false) -> bool [T, S]: of each row's seen columns the ``k`` with the
    largest scores, a tie to the lower column; all of them where they are
    ``k`` or fewer. No sort: the row's ``k``-th largest score is built from
    the top, ``_BITS_A_PASS`` bits a pass (a pass reads the scores once and
    counts, for each value the digit could take, the entries at or above it),
    and the columns above it are kept, with as many of those equal to it, lowest
    first, as bring the count to ``k`` (a pass of its own, run only where some
    row has more equals than places)."""
    T, S = scores.shape
    if k >= S:
        return seen
    key = _ordered_bits(scores)
    digits = (1 << _BITS_A_PASS) - 1

    def digit(i, prefix):
        shift = (32 - _BITS_A_PASS * (i + 1)).astype(jnp.uint32)
        # a row's count of keys at or above a candidate falls as the candidate
        # rises, so the digit is the number of candidates with enough
        enough = [
            jnp.sum(key >= (prefix | (jnp.uint32(j) << shift))[:, None], axis=1, dtype=jnp.int32) >= k
            for j in range(1, digits + 1)
        ]
        return prefix | (sum(e.astype(jnp.uint32) for e in enough) << shift)

    kth = jax.lax.fori_loop(0, 32 // _BITS_A_PASS, digit, jnp.zeros((T,), jnp.uint32))[:, None]
    above, equal = key > kth, key == kth
    places = k - jnp.sum(above, axis=1, dtype=jnp.int32)  # >= 1 for the equals
    crowded = (jnp.sum(equal, axis=1, dtype=jnp.int32) > places) & (kth[:, 0] > _NEG_INF_KEY)
    last_equal = jax.lax.cond(
        jnp.any(crowded),
        lambda: jnp.argmax(jnp.cumsum(equal, axis=1, dtype=jnp.int32) >= places[:, None], axis=1),
        lambda: jnp.full((T,), S, jnp.int32),
    )
    return seen & (above | (equal & (jnp.arange(S)[None, :] <= last_equal[:, None])))


@stage("attn_select")
def select_prefill(qi, w, ikv, l: int, table, pos, n_keys, k: int, *, block_size: int):
    """The positions each query of a prefill keeps, bool [T, W block]:
    ``qi`` [T, J, d_I] and ``w`` [T, J] at consecutive positions ``pos`` [T]
    against layer ``l`` of the index-key pool ``ikv`` [L, N, block, d_I]
    (which already holds their own keys) through ``table`` [W], a stretch of
    ``KEY_POSITIONS`` at a time up to the last position that holds a key
    (``n_keys``, traced); then :func:`kept_mask` under ``column <= position``."""
    T = qi.shape[0]
    S = table.shape[0] * block_size
    nb = math.gcd(table.shape[0], max(1, KEY_POSITIONS // block_size))
    Kb = nb * block_size

    def step(j, scores):
        blocks = jax.lax.dynamic_slice_in_dim(table, j * nb, nb)
        s = _scores(qi, w, ikv[l, blocks].reshape(Kb, -1))
        return jax.lax.dynamic_update_slice_in_dim(scores, s, j * Kb, axis=1)

    scores = jax.lax.fori_loop(0, _steps(pos, n_keys, Kb), step, jnp.full((T, S), -jnp.inf, _F32))
    seen = jnp.arange(S)[None, :] <= pos[:, None]
    return kept_mask(jnp.where(seen, scores, -jnp.inf), seen, k)


@stage("attn_select")
def select_decode(qi, w, ikv, l: int, tables, lengths, k: int):
    """The rows each slot's query keeps: ``(idx [B, k] int32 positions, kept
    [B, k] bool)``; ``qi`` [B, J, d_I], ``w`` [B, J] against the first
    ``lengths`` [B] index keys that ``tables`` [B, W] give each slot in layer
    ``l``. ``lax.top_k`` puts the lower column first among equals. A slot with
    ``k`` rows or fewer keeps them all: the places behind them name position 0
    and are not ``kept``."""
    B, W = tables.shape
    S = W * ikv.shape[2]
    keys = ikv[l, tables].reshape(B, S, -1)
    scores = jnp.where(jnp.arange(S)[None, :] < lengths[:, None], _scores(qi, w, keys), -jnp.inf)
    vals, idx = jax.lax.top_k(scores, min(k, S))
    kept = vals > -jnp.inf
    return jnp.where(kept, idx, 0).astype(jnp.int32), kept


# ---------------------------------------------------------------------------
# Attention over the selected rows


def attend_selected(q, ckv, l: int, table, pos, n_keys, keep, p, cfg, *, block_size: int, interpret: bool = False):
    """:func:`ray_tpu.models.latent_moe.mla_prefill`'s fold under one more
    mask: ``q`` [T, H, d_n + d_r] rotated queries at ``pos`` [T], keys and
    values expanded per head a stretch of the table at a time and folded into
    a running softmax under ``keep`` [T, W block] (which lies inside ``column
    <= position``), up to the last position that holds a row. Two arms,
    chosen as :func:`ray_tpu.models.paged._choose` says: where the program is
    lowered for a TPU and the shapes are the kernel's
    (:func:`ray_tpu.ops.selected_attention.fits`) the whole fold is one
    Pallas call, which is handed the queries, ``wkvb``, the pool, the table
    and the mask as they lie and walks the live stretches of
    ``KERNEL_KEY_POSITIONS`` itself; :func:`_fold_selected`'s XLA einsums
    over stretches of ``KEY_POSITIONS`` elsewhere. ``interpret`` runs the
    kernel in the Pallas interpreter whatever the platform and the shapes
    (the tests)."""
    T = q.shape[0]
    H, dv, dt = cfg.n_head, cfg.v_head_dim, cfg.dtype
    blocks = lambda positions: math.gcd(table.shape[0], max(1, positions // block_size))  # noqa: E731
    static = dict(cfg=cfg, block_size=block_size)
    fits = selected_attention.fits(
        H, T, blocks(KERNEL_KEY_POSITIONS) * block_size, dv, dt,
        nope=cfg.qk_nope_head_dim, rank=cfg.kv_lora_rank, block_size=block_size,
    )
    with stage("attn_core"):
        operands = (q, ckv, jnp.asarray(l, jnp.int32), table, pos, jnp.asarray(n_keys, jnp.int32), keep,
                    p["wkvb"].astype(dt))
        o = paged._choose(
            functools.partial(_kernel_selected, nb=blocks(KERNEL_KEY_POSITIONS), **static),
            functools.partial(_fold_selected, nb=blocks(KEY_POSITIONS), **static), fits, interpret,
        )(*operands)
    with stage("attn_proj"):
        return o.reshape(T, H * dv) @ p["wo"].astype(dt)


def _stretch(ckv, l, table, j, wkvb, cfg, nb: int, block_size: int):
    """Stretch ``j`` of the table expanded per head: ``(k [H, Kb, d_n + d_r],
    v [H, Kb, d_v])``, the rotated shared key behind every head's own."""
    H, dn, dv = cfg.n_head, cfg.qk_nope_head_dim, cfg.v_head_dim
    R, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    Kb = nb * block_size
    rows = ckv[l, jax.lax.dynamic_slice_in_dim(table, j * nb, nb)].reshape(Kb, -1)
    kv = jnp.einsum("sr,rhd->hsd", rows[:, :R], wkvb.reshape(R, H, dn + dv))
    k_r = jnp.broadcast_to(rows[None, :, R : R + dr], (H, Kb, dr))  # one for all heads
    return jnp.concatenate([kv[..., :dn], k_r], axis=-1), kv[..., dn:]


def _steps(pos, n_keys, Kb: int):
    """Stretches of ``Kb`` positions up to the one that holds the last
    query's own position, or the last that holds a row (``n_keys``, traced)."""
    return (jnp.minimum(n_keys - 1, pos[-1]) // Kb + 1).astype(jnp.int32)


def _kernel_selected(q, ckv, l, table, pos, n_keys, keep, wkvb, *, cfg, nb, block_size, interpret=False):
    """[T, H, d_v]: :func:`ray_tpu.ops.selected_attention.attend`, one call
    that walks the live stretches of ``nb`` blocks itself."""
    return selected_attention.attend(
        q, wkvb, ckv, l, table, keep, _steps(pos, n_keys, nb * block_size),
        scale=cfg.softmax_scale, nope=cfg.qk_nope_head_dim, pages=nb, interpret=interpret,
    )


def _fold_selected(q, ckv, l, table, pos, n_keys, keep, wkvb, *, cfg, nb, block_size):
    """[T, H, d_v] in XLA's own operations: the plain arm, all the queries
    through every stretch up to the last one's position."""
    T, H, dv, dt = q.shape[0], cfg.n_head, cfg.v_head_dim, cfg.dtype
    Kb = nb * block_size

    def step(j, carry):
        m, s_sum, acc = carry
        k, v = _stretch(ckv, l, table, j, wkvb, cfg, nb, block_size)
        s = jnp.einsum("thd,hsd->hts", q, k, preferred_element_type=_F32) * cfg.softmax_scale
        kept = jax.lax.dynamic_slice_in_dim(keep, j * Kb, Kb, axis=1)
        s = jnp.where(kept[None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alive = jnp.exp(m - m_new)
        # (a stretch that holds none of a row's positions adds exp(0) a column
        # while the row's maximum is still -1e30; the first that holds one
        # multiplies that away, and every row keeps a position)
        e = jnp.exp(s - m_new[..., None])
        acc = acc * alive[..., None] + jnp.einsum("hts,hsd->htd", e.astype(dt), v, preferred_element_type=_F32)
        return m_new, s_sum * alive + jnp.sum(e, axis=-1), acc

    init = (jnp.full((H, T), -1e30, _F32), jnp.zeros((H, T), _F32), jnp.zeros((H, T, dv), _F32))
    _, s_sum, acc = jax.lax.fori_loop(0, _steps(pos, n_keys, Kb), step, init)
    return (acc / s_sum[..., None]).astype(dt).transpose(1, 0, 2)


def attend_rows(idx, kept, block_size: int, value_width: int, scale: float):
    """``attend(ql, ckv, l, tables, lengths)`` for
    :func:`ray_tpu.models.latent_moe.mla_decode` over the rows ``idx`` [B, k]
    of each slot alone, gathered one by one through its table; ``kept`` [B, k]
    masks the places that name no row. ``lengths`` is not asked: the
    selection lies inside it."""

    def attend(ql, ckv, l, tables, lengths):
        B = ql.shape[0]
        bids = tables[jnp.arange(B)[:, None], idx // block_size]
        rows = ckv[l, bids, idx % block_size]  # [B, k, C]
        s = jnp.einsum("bhc,bkc->bhk", ql, rows).astype(_F32) * scale
        pa = jax.nn.softmax(jnp.where(kept[:, None], s, -1e30), axis=-1).astype(rows.dtype)
        return jnp.einsum("bhk,bkr->bhr", pa, rows[..., :value_width])

    return attend


# ---------------------------------------------------------------------------
# The paged programs (models/paged.py dispatches here by cfg.family)


def cache(cfg: DeepseekV32Config) -> paged.Cache:
    """Latent rows and index keys in blocks under one table, and nothing by
    slot: a prefix is shared by block ids, both parts at once. Decode reads
    rows chosen one by one, which neither arm of
    :func:`ray_tpu.models.paged.latent_decode_attention` does."""
    return paged.Cache(per_head=False, selects_rows=True)


def init_pool(cfg: DeepseekV32Config, num_blocks: int, block_size: int, slots=None):
    """The zeroed cache: ``mla_moe``'s latent rows and, beside them, the
    index keys, one part each for all layers. ``slots`` sizes nothing."""
    shape = (cfg.n_layer, num_blocks, block_size)
    return {
        "ckv": jnp.zeros((*shape, cfg.pool_row_dim), cfg.dtype),
        "ikv": jnp.zeros((*shape, cfg.index_head_dim), cfg.dtype),
    }


def _low_rank_query(x, p, cfg):
    """``(h, c_q)``: the normed hidden state and the query's normed low-rank
    part, which MLA's queries and the index queries are both made from."""
    with stage("attn_proj"):
        h = _rms_norm(x, p["attn_norm"], cfg.rms_eps)
        return h, _rms_norm(h @ p["wq_a"].astype(cfg.dtype), p["q_norm"], cfg.rms_eps)


def _from_c_q(p):
    """The layer's parameters as ``latent_moe`` reads them when it is handed
    ``c_q`` in the hidden state's place: one matrix to the heads."""
    return {"wq": p["wq_b"], "wkvb": p["wkvb"], "wo": p["wo"]}


def paged_prefill(
    params, tokens, length, start, table, pool, cfg: DeepseekV32Config, *,
    block_size: int, slot=None, with_picks: bool = False, with_selection: bool = False,
    interpret: bool = False,
):
    """Prefill positions [start, start + T) of one sequence; operands as
    :func:`ray_tpu.models.paged.paged_prefill` (``slot`` names nothing here).
    Each layer writes the chunk's latent rows and index keys, then, a run of
    ``SELECT_QUERIES`` queries at a time, scores every index key the table
    holds by now and attends what each query keeps.
    Returns ``(pool, last_logits [vocab] float32, counts int32 [expert
    layers + 1, 2])``: ``mla_moe``'s counters and, behind them, ``(start,
    length)`` for the span's ``index_pairs_scored`` (:func:`span_fields`);
    with ``with_picks`` the chosen experts, with ``with_selection`` the kept
    positions bool [L, T, W block], behind them. ``interpret`` runs the
    attention's kernel arm in the Pallas interpreter (the tests)."""
    T = tokens.shape[1]
    ckv, ikv = pool["ckv"], pool["ikv"]
    pos = start + jnp.arange(T, dtype=jnp.int32)
    valid = jnp.arange(T) < length
    n_keys = start + length
    with stage("attn_proj"):
        rope = mla_moe._rope(cfg, pos)
    with stage("pool_write"):
        bids, offs = table[pos // block_size], pos % block_size
    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[tokens[0]]
    seen: list = []
    kept: list = []
    for l, p in enumerate(params["layers"]):
        h, c_q = _low_rank_query(x, p, cfg)
        row = mla_latent(h, p, cfg, rope, cfg.pool_row_dim)
        key = index_key(h, p, cfg, rope)
        with stage("pool_write"):
            ckv, ikv = ckv.at[l, bids, offs].set(row), ikv.at[l, bids, offs].set(key)
        qi, w = index_query(h, c_q, p, cfg, rope)
        q = mla_query(c_q, _from_c_q(p), cfg, rope)
        keeps, outs = [], []
        for run in (slice(i, i + SELECT_QUERIES) for i in range(0, T, SELECT_QUERIES)):
            keep = select_prefill(
                qi[run], w[run], ikv, l, table, pos[run], n_keys, cfg.index_topk, block_size=block_size
            )
            outs.append(attend_selected(
                q[run], ckv, l, table, pos[run], n_keys, keep, p, cfg, block_size=block_size, interpret=interpret
            ))
            keeps.append(keep)
        with stage("attn_proj"):
            x = x + jnp.concatenate(outs)
        x = ffn(x, p, cfg, l + 1, valid, seen)
        if with_selection:
            kept.append(jnp.concatenate(keeps))
    with stage("embed_head"):
        last = jax.lax.dynamic_index_in_dim(x, (length - 1).astype(jnp.int32), 0, keepdims=False)
    logits = final_logits(params, last[None], cfg)[0]
    return _outputs({"ckv": ckv, "ikv": ikv}, logits, seen, [start, length], with_picks,
                    kept if with_selection else None)


def paged_decode(
    params, last_tokens, positions, tables, pool, cfg: DeepseekV32Config, *,
    block_size: int, live=None, with_picks: bool = False, with_selection: bool = False,
):
    """One token a slot; operands as :func:`ray_tpu.models.mla_moe.paged_decode`
    (no kernel here, so no ``interpret``). Each layer writes the
    step's latent row and index key, scores the slot's index keys [0,
    position], and attends the ``index_topk`` rows that score highest (all of
    them up to that many), gathered by position. Returns ``(pool, logits [B,
    vocab] float32, counts)``; with ``with_selection`` also ``(idx, kept)``
    [L, B, k] a layer."""
    B = last_tokens.shape[0]
    ckv, ikv = pool["ckv"], pool["ikv"]
    with stage("pool_write"):
        bids = tables[jnp.arange(B), positions // block_size]
        offs = positions % block_size
    with stage("attn_select"):
        lengths = positions + 1  # the step's own row can be kept
    with stage("attn_proj"):
        rope = mla_moe._rope(cfg, positions)
    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[last_tokens]
    seen: list = []
    chosen: list = []
    for l, p in enumerate(params["layers"]):
        h, c_q = _low_rank_query(x, p, cfg)
        row = mla_latent(h, p, cfg, rope, cfg.pool_row_dim)
        key = index_key(h, p, cfg, rope)
        with stage("pool_write"):
            ckv, ikv = ckv.at[l, bids, offs].set(row), ikv.at[l, bids, offs].set(key)
        qi, w = index_query(h, c_q, p, cfg, rope)
        idx, kept = select_decode(qi, w, ikv, l, tables, lengths, cfg.index_topk)
        attend = attend_rows(idx, kept, block_size, cfg.kv_lora_rank, cfg.softmax_scale)
        out = mla_decode(c_q, ckv, l, tables, lengths, _from_c_q(p), cfg, attend, rope)
        with stage("attn_proj"):
            x = x + out
        x = ffn(x, p, cfg, l + 1, live, seen)
        chosen.append((idx, kept))
    return _outputs({"ckv": ckv, "ikv": ikv}, final_logits(params, x, cfg), seen, None, with_picks,
                    chosen if with_selection else None)


def _outputs(pool, logits, seen, chunk, with_picks: bool, selection):
    """:func:`ray_tpu.models.latent_moe.outputs`, a prefill's ``(start,
    length)`` behind the expert layers' counters and the selection, asked
    for, behind everything."""
    pool, logits, counts, *picks = latent_moe.outputs(pool, logits, seen, with_picks)
    if chunk is not None:
        with stage("embed_head"):
            counts = jnp.concatenate([counts, jnp.stack(chunk).astype(jnp.int32)[None]])
    out = (pool, logits, counts, *picks)
    if selection is not None:
        out += (jax.tree.map(lambda *a: jnp.stack(a), *selection),)
    return out


def span_fields(cfg: DeepseekV32Config, counts, tokens: int, slots: int, decode=None) -> dict:
    """:func:`ray_tpu.models.latent_moe.span_fields` and the selection's
    counters, a layer each. A decode step (``decode``: the live slots'
    positions, and the rows either arm of the latent kernel would read, which
    this family's program does not run): ``index_rows_scored``, the index
    keys a step's queries score (each live slot's ``position + 1``: what is
    needed; the program gathers every slot's whole table), ``latent_rows_selected``
    (``min(position + 1, index_topk)`` summed) and ``latent_rows_read``, the
    ``index_topk`` places a live slot's gather fills. A prefill, whose
    counters end in ``(start, length)``: ``index_pairs_scored`` (query ``t``
    scores ``t + 1`` keys) and ``latent_rows_selected``."""
    k = cfg.index_topk
    out = latent_moe.span_fields(cfg, counts, tokens, decode)
    if decode is not None:
        rows = np.asarray(decode[0], np.int64) + 1
        out["index_rows_scored"] = int(rows.sum())
        out["latent_rows_read"] = len(rows) * min(k, cfg.max_seq)
    else:
        n = 2 * cfg.n_moe_layers
        start, length = (int(v) for v in counts[n : n + 2])
        rows = start + 1 + np.arange(length, dtype=np.int64)
        out["index_pairs_scored"] = int(rows.sum())
    out["latent_rows_selected"] = int(np.minimum(rows, k).sum())
    return out
