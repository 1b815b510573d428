"""Granite 4.0-H (``model_type: granitemoehybrid``; granite-4.0-h-micro): a
dense hybrid decoder. Every layer is two sublayers, ``x += r Mixer(RMSNorm(x))``
then ``x += r MLP(RMSNorm(x))`` with ``r = residual_multiplier``; the mixer by
``layer_types``: ``mamba`` a Mamba-2 state-space layer of ONE group, ``attention``
grouped-query attention of 64-wide heads with no rotation and no other position
signal, its scores times ``attention_multiplier`` (which replaces
``head_dim^-1/2``); the MLP a SwiGLU. ``x_0 = embedding_multiplier E[token]``;
final RMSNorm; the head is the embedding, ``logits = RMSNorm(x) E^T /
logits_scaling``. Ninth family of the serving tier, the first that is dense
with a state per slot, and the first whose programs trace a *period* of the
layer pattern and scan it.

The two mixers are :mod:`ray_tpu.models.nemotron_h`'s (``mamba_prefill``,
``mamba_decode``, ``_qkv``, ``causal_attention``), called with this family's
configuration, whose fields bear the names they read.

**A period, scanned.** ``layer_types`` repeats (``M M M M M A M M M M`` four
times over for the published model): :attr:`GraniteHybridConfig.period` is its
shortest repeating unit, the parameters of like places are stacked ``[periods,
...]`` (``params["period"][place]``), and both programs are one ``lax.scan``
over the periods whose body walks the places, the pool in the carry
(:func:`ray_tpu.models.paged._scan_layers`' way) and a layer's index into
``state``, ``conv`` and ``kv`` a traced scalar. A program holds one period's
Mosaic calls whatever the depth. ``unrolled=True`` walks the same layers one by
one (the tests hold the two equal; the chip timings are in PERF.md section 6).

**The cache** is ``{"kv": [A, N, KH, block, 128], "state": [M, slots + 1, H, P,
N] float32, "conv": [M, slots + 1, 3 (H P + 2 N)]}``: a position's value and
key of a head side by side in one row of 128 lanes
(``paged.AttentionKind.packed``: heads of 64 attended in place, no lane
padded), and a Mamba-2 state and convolution tail per slot
(:mod:`ray_tpu.models.paged`, "What a pool is made of"). Row ``slots`` of the
last two is scratch. A slot's tail is one flat row: a prefill of 128 tokens or
more keeps its activations position-minor, and the compiler then wants the
whole of a ``[.., 3, C]`` pool with the 3 along lanes, a copy of 42 times its
bytes in and out of every prefill (2.6 GB here: PERF.md section 6, PR 51).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from ray_tpu.models import nemotron_h, paged
from ray_tpu.models.common import _rms_norm, stage

Params = dict
_F32 = jnp.float32

PUBLISHED_LAYER_TYPES = (("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """Published key meanings (``config.json``); defaults are the published
    granite-4.0-h-micro sizes, uncut. The Mamba-2 and attention fields bear
    :class:`ray_tpu.models.nemotron_h.NemotronHConfig`'s names: its mixers
    read them."""

    family: ClassVar[str] = "granitemoehybrid"

    vocab_size: int = 100352
    d_model: int = 2048
    layer_types: tuple = PUBLISHED_LAYER_TYPES
    # Mamba-2
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    ssm_groups: int = 1  # mamba_n_groups: of B, C and the gated norm
    ssm_state: int = 128
    conv_kernel: int = 4
    time_step_min: float = 0.001  # no time_step_* keys are published: the family's defaults
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # Attention
    n_head: int = 32
    n_kv_head: int = 8
    head_dim: int = 64
    attention_multiplier: float = 0.015625  # the scores' scale: it replaces head_dim^-1/2
    # The SwiGLU
    d_ff: int = 8192  # shared_intermediate_size
    # The four multipliers
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    # Serving
    max_seq: int = 2048
    state_slots: int = 16  # state rows where the caller names no count
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        assert set(self.layer_types) <= {"mamba", "attention"}, self.layer_types
        assert self.n_head % self.n_kv_head == 0 and self.mamba_heads % self.ssm_groups == 0

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def period(self) -> tuple:
        """The shortest unit that ``layer_types`` repeats."""
        kinds = self.layer_types
        return next(
            kinds[:n] for n in range(1, len(kinds) + 1)
            if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n)
        )

    @property
    def periods(self) -> int:
        return self.n_layer // len(self.period)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @staticmethod
    def tiny(layer_types=("mamba", "mamba", "attention", "mamba") * 2, vocab_size: int = 512,
             max_seq: int = 256, **kw) -> "GraniteHybridConfig":
        """A CPU-test size with the published shape: a period with the
        attention layer inside it, turned twice; one group; every multiplier
        off 1."""
        return GraniteHybridConfig(**{**dict(
            vocab_size=vocab_size, d_model=64, layer_types=tuple(layer_types),
            mamba_heads=8, mamba_head_dim=8, ssm_groups=1, ssm_state=16,
            n_head=4, n_kv_head=2, head_dim=16, attention_multiplier=1 / 16, d_ff=96,
            embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0,
            max_seq=max_seq, state_slots=4, dtype=jnp.float32, param_dtype=jnp.float32,
        ), **kw})


# ---------------------------------------------------------------------------
# Parameters


def qk_std(cfg: GraniteHybridConfig) -> float:
    """The deviation ``W_q`` and ``W_k`` are drawn with, so that a score after
    the multiplier has a deviation near 1 over a normed input of unit mean
    square: ``Var(score) = (multiplier^2) head_dim (d_model sigma^2)^2``."""
    return (cfg.attention_multiplier * cfg.head_dim**0.5 * cfg.d_model) ** -0.5


@functools.partial(jax.jit, static_argnames="cfg")
def init_params(key: jax.Array, cfg: GraniteHybridConfig) -> Params:
    """Random weights (:func:`draw_params`); one program, which the compile
    cache keeps."""
    return draw_params(key, cfg)


def draw_params(key: jax.Array, cfg: GraniteHybridConfig) -> Params:
    """Random weights, drawn tensor by tensor in the parameter dtype (no
    float32 copy of a stack is ever live), each place of the period stacked
    over the periods. N(0, 0.02); the projections that write to the residual
    stream (``w_out``, ``wo``, ``w_down``) scaled by 1/sqrt(2 layers), one
    branch a sublayer, as ``rescale_prenorm_residual`` scales them; ``wq`` and
    ``wk`` N(0, :func:`qk_std`): a served model's softmax is not flat, and with
    0.02 the published multiplier of 1/64 would leave every row of it uniform;
    the Mamba-2 parameters as :func:`ray_tpu.models.nemotron_h.draw_params`
    draws them; norms one."""
    pd = cfg.param_dtype
    D, H, K, F, n = cfg.d_model, cfg.mamba_heads, cfg.conv_kernel, cfg.d_ff, cfg.periods
    std = 0.02
    resid = std / (2 * cfg.n_layer) ** 0.5
    keys = iter(jax.random.split(key, 12 * len(cfg.period) + 2))

    def w(shape, s=std):
        return jax.random.normal(next(keys), shape, pd) * jnp.asarray(s, pd)

    def mamba():
        step = jnp.exp(jax.random.uniform(
            next(keys), (n, H), _F32, jnp.log(cfg.time_step_min), jnp.log(cfg.time_step_max)))
        step = jnp.maximum(step, cfg.time_step_floor)
        conv = jax.random.uniform(next(keys), (n, K + 1, cfg.conv_dim), _F32, -(K**-0.5), K**-0.5)
        return {
            "w_in": w((n, D, cfg.d_inner + cfg.conv_dim + H)),
            "conv_w": conv[:, :K].astype(pd), "conv_b": conv[:, K].astype(pd),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1(step)
            "A_log": jnp.log(jax.random.uniform(next(keys), (n, H), _F32, 1.0, 16.0)),
            "D": jnp.ones((n, H), _F32),
            "gate_norm": jnp.ones((n, cfg.d_inner), pd),
            "w_out": w((n, cfg.d_inner, D), resid),
        }

    def attention():
        qk = qk_std(cfg)
        return {
            "wq": w((n, D, cfg.n_head * cfg.head_dim), qk),
            "wk": w((n, D, cfg.n_kv_head * cfg.head_dim), qk),
            "wv": w((n, D, cfg.n_kv_head * cfg.head_dim)),
            "wo": w((n, cfg.n_head * cfg.head_dim, D), resid),
        }

    mixers = {"mamba": mamba, "attention": attention}
    return {
        "wte": w((cfg.vocab_size, D)),
        "period": [
            {
                "norm": jnp.ones((n, D), pd), **mixers[kind](),
                "mlp_norm": jnp.ones((n, D), pd),
                "w_gate_up": w((n, D, 2 * F)), "w_down": w((n, F, D), resid),
            }
            for kind in cfg.period
        ],
        "final_norm": jnp.ones((D,), pd),
    }


# ---------------------------------------------------------------------------
# The attention mixer over the packed pool, the SwiGLU, the head


def attention_kind(cfg: GraniteHybridConfig) -> paged.AttentionKind:
    """Heads of 64 for keys and values alike, side by side in one pool row,
    scored at the published multiplier."""
    return paged.AttentionKind(
        cfg.n_kv_head, cfg.head_dim, cfg.head_dim, jnp.dtype(cfg.dtype).itemsize,
        layers=cfg.layer_types.count("attention"), scale=cfg.attention_multiplier, packed=True,
    )


def attention_prefill(u, p, cfg: GraniteHybridConfig, kv, l, table, pos, block_size: int):
    """:func:`ray_tpu.models.nemotron_h.attention_prefill` over the packed
    pool: ``[value | key]`` rows written under ``table`` [W] and the table's
    row gathered back. Returns ``(out [T, D], kv)``."""
    KH, Dh = cfg.n_kv_head, cfg.head_dim
    S = table.shape[0] * block_size
    q, k, v = nemotron_h._qkv(u, p, cfg)
    with stage("attn_proj"):
        row = jnp.concatenate([v, k], axis=-1)
    kv, rows = paged._write_blocks_read(kv, l, table, pos[0], row, block_size)
    with stage("attn_core"):
        rows = rows.transpose(1, 0, 2, 3).reshape(KH, S, 2 * Dh)
        keys, values = rows[..., Dh:], rows[..., :Dh]
    o = nemotron_h.causal_attention(q, keys, values, pos, cfg, cfg.attention_multiplier)
    with stage("attn_proj"):
        return o @ p["wo"].astype(cfg.dtype), kv


def attention_decode(u, p, cfg: GraniteHybridConfig, kv, l, tables, positions, block_size, attend):
    """One query a slot: its ``[value | key]`` row written at ``positions``
    [B] under ``tables`` [B, W], then positions [0, position] attended by
    ``attend`` (:func:`ray_tpu.models.paged.packed_decode_attention`). Returns
    ``(out [B, D], kv)``."""
    B = u.shape[0]
    q, k, v = nemotron_h._qkv(u, p, cfg)
    with stage("pool_write"):
        bids, offs = tables[jnp.arange(B), positions // block_size], positions % block_size
    with stage("attn_proj"):
        row = jnp.concatenate([v, k], axis=-1)
    kv = paged._write(kv, l, bids, offs, row)
    with stage("attn_core"):
        o = attend(q, kv, jnp.asarray(l, jnp.int32), tables, positions + 1)
    with stage("attn_proj"):
        return o.reshape(B, -1) @ p["wo"].astype(cfg.dtype), kv


@stage("mlp")
def mlp(m, p, cfg: GraniteHybridConfig):
    """``W_down (silu(W_gate m) * W_up m)``, gate and up one matrix."""
    dt = cfg.dtype
    gate, up = jnp.split(m @ p["w_gate_up"].astype(dt), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ p["w_down"].astype(dt)


@stage("embed_head")
def final_logits(params, last, cfg: GraniteHybridConfig):
    """The tied head: ``RMSNorm(x) E^T / logits_scaling``, float32."""
    h = _rms_norm(last, params["final_norm"], cfg.rms_eps)
    logits = jnp.einsum("...d,vd->...v", h, params["wte"].astype(cfg.dtype), preferred_element_type=_F32)
    return logits / cfg.logits_scaling


# ---------------------------------------------------------------------------
# What the engine writes on a span


def span_fields(cfg: GraniteHybridConfig, counts, tokens: int, slots: int, decode=None) -> dict:
    """The rows of the state that the run stepped (``slots`` sequences) and
    the layers whose state it stepped; the programs have no counters."""
    return {"state_slots": slots, "state_layers": cfg.layer_types.count("mamba")}


# ---------------------------------------------------------------------------
# The paged programs (models/paged.py dispatches here by cfg.family)


def cache(cfg: GraniteHybridConfig) -> paged.Cache:
    """Values and keys per head in blocks, one pool row a position and head
    (the attention layers'), a Mamba-2 state and a tail per slot."""
    return paged.Cache(slot_state=True, kinds=(attention_kind(cfg),))


def init_pool(cfg: GraniteHybridConfig, num_blocks: int, block_size: int, slots=None):
    """The zeroed cache (docstring of this module)."""
    slots = cfg.state_slots if slots is None else slots
    n_m, n_a = cfg.layer_types.count("mamba"), cfg.layer_types.count("attention")
    return {
        "kv": jnp.zeros((n_a, num_blocks, cfg.n_kv_head, block_size, 2 * cfg.head_dim), cfg.dtype),
        "state": jnp.zeros((n_m, slots + 1, cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state), _F32),
        "conv": jnp.zeros((n_m, slots + 1, (cfg.conv_kernel - 1) * cfg.conv_dim), cfg.dtype),
    }


def _walk(period_body, x, params, pool, cfg: GraniteHybridConfig, unrolled: bool):
    """``period_body(x, pool, places, i) -> (x, pool)`` over the periods:
    scanned, the pool in the carry, ``places`` the period's parameters and
    ``i`` its index a traced scalar; or, ``unrolled``, one period after
    another with ``i`` a number."""
    if unrolled:
        for i in range(cfg.periods):
            x, pool = period_body(x, pool, jax.tree.map(lambda a: a[i], params["period"]), i)
        return x, pool

    def body(carry, layer):
        return period_body(*carry, *layer), None

    (x, pool), _ = jax.lax.scan(
        body, (x, pool), (params["period"], jnp.arange(cfg.periods, dtype=jnp.int32))
    )
    return x, pool


def _period(cfg: GraniteHybridConfig, mamba, attention):
    """The body of a period: each place's mixer (``mamba(u, p, pool, l)`` or
    ``attention(u, p, pool, l)`` -> ``(out, pool)``, ``l`` the layer's index
    among those of its kind) and its SwiGLU, each branch times the residual
    multiplier. The mixers name their own stages; a norm goes with the stage
    it feeds and a residual with the stage that made the branch."""
    n_m, n_a = cfg.period.count("mamba"), cfg.period.count("attention")
    r = cfg.residual_multiplier

    def body(x, pool, places, i):
        seen = {"mamba": 0, "attention": 0}
        for kind, p in zip(cfg.period, places):
            mixer_in, mixer_out = ("state_in", "state_out") if kind == "mamba" else ("attn_proj", "attn_proj")
            with stage(mixer_in):
                u = _rms_norm(x, p["norm"], cfg.rms_eps)
            if kind == "mamba":
                out, pool = mamba(u, p, pool, i * n_m + seen[kind])
            else:
                out, pool = attention(u, p, pool, i * n_a + seen[kind])
            seen[kind] += 1
            with stage(mixer_out):
                x = x + (r * out).astype(x.dtype)
            with stage("mlp"):
                m = _rms_norm(x, p["mlp_norm"], cfg.rms_eps)
            out = mlp(m, p, cfg)
            with stage("mlp"):
                x = x + (r * out).astype(x.dtype)
        return x, pool

    return body


def paged_prefill(
    params, tokens, length, start, table, pool, cfg: GraniteHybridConfig, *,
    block_size: int, slot=None, unrolled: bool = False,
):
    """Prefill positions [start, start + T) of one sequence; operands as
    :func:`ray_tpu.models.nemotron_h.paged_prefill`: ``start == 0`` begins from
    zero state and an empty tail whatever the slot held, ``start > 0``
    continues from the slot's (a later chunk). Returns ``(pool, last_logits
    [vocab] float32)``."""
    T = tokens.shape[1]
    fresh = start == 0
    pos = start + jnp.arange(T, dtype=jnp.int32)

    def mamba(u, p, pool, l):
        def step(h, tail):  # the slot's tail is one flat row (docstring of this module)
            with stage("state_scan"):
                tail = tail.reshape(-1, cfg.conv_dim)
            out, h, tail = nemotron_h.mamba_prefill(u, p, cfg, h, tail, length)
            with stage("state_scan"):
                return out, h, tail.reshape(-1)

        out, state, conv = paged.state_prefill(step, pool["state"], pool["conv"], l, slot, fresh)
        return out, {**pool, "state": state, "conv": conv}

    def attention(u, p, pool, l):
        out, kv = attention_prefill(u, p, cfg, pool["kv"], l, table, pos, block_size)
        return out, {**pool, "kv": kv}

    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[tokens[0]] * jnp.asarray(cfg.embedding_multiplier, cfg.dtype)
    x, pool = _walk(_period(cfg, mamba, attention), x, params, pool, cfg, unrolled)
    with stage("embed_head"):
        last = jax.lax.dynamic_index_in_dim(x, (length - 1).astype(jnp.int32), 0, keepdims=False)
    return pool, final_logits(params, last[None], cfg)[0]


def paged_decode(
    params, last_tokens, positions, tables, pool, cfg: GraniteHybridConfig, *,
    block_size: int, live=None, interpret: bool = False, unrolled: bool = False,
):
    """One token a slot; operands as
    :func:`ray_tpu.models.nemotron_h.paged_decode`: a slot that is not live
    leaves its state and tail as they were. ``interpret`` runs both kernels in
    the Pallas interpreter (the tests). Returns ``(pool, logits [B, vocab]
    float32)``."""
    B = last_tokens.shape[0]
    attend = paged.packed_decode_attention(attention_kind(cfg), block_size, None, interpret)
    with stage("state_scan"):
        keep = None if live is None else ~live

    def mamba(u, p, pool, l):
        def step(h, tail):
            with stage("state_scan"):
                tail = tail.reshape(B, -1, cfg.conv_dim)
            out, h, tail = nemotron_h.mamba_decode(u, p, cfg, h, tail)
            with stage("state_scan"):
                return out, h, tail.reshape(B, -1)

        out, state, conv = paged.state_decode(
            step, pool["state"], pool["conv"], l, B, keep, interpret=interpret
        )
        return out, {**pool, "state": state, "conv": conv}

    def attention(u, p, pool, l):
        out, kv = attention_decode(u, p, cfg, pool["kv"], l, tables, positions, block_size, attend)
        return out, {**pool, "kv": kv}

    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[last_tokens] * jnp.asarray(cfg.embedding_multiplier, cfg.dtype)
    x, pool = _walk(_period(cfg, mamba, attention), x, params, pool, cfg, unrolled)
    return pool, final_logits(params, x, cfg)
