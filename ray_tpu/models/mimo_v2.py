"""MiMo-V2 (``model_type: mimo_v2``; XiaomiMiMo MiMo-V2-Flash / MiMo-V2.5): a
decoder whose attention layers are of two kinds that differ in *shape*, five
that attend a sliding window of 128 positions with a learned sink to one that
attends everything, with a routed mixture of experts and no shared one behind
a leading dense layer. Eighth model family of the serving tier, and the first
whose two parts of the cache have different head counts, and whose keys are
wider than its values.

The layer (pre-norm residuals: ``x += Attn(RMSNorm(x))``, ``x +=
FFN(RMSNorm(x))``; final RMSNorm; untied head; no biases):

- ``x0 = E[token]``.
- **Attention.** ``a = RMSNorm(x)``; ``q = W_q a`` [H, Dk], ``k = W_k a`` [KH,
  Dk], ``v = value_scale W_v a`` [KH, Dv], with ``Dk = 192`` and ``Dv = 128``
  as published and ``KH`` by the layer's kind (4 in a full layer, 8 in a
  window layer). The first ``rotary_dim`` (64) lanes of each ``q`` and ``k``
  head are rotated by position, in halves (lane ``i`` with ``i + 32``), with
  the base of the layer's kind; the other lanes are not. Scores ``q.k
  Dk^-1/2`` in float32, causal, ``H / KH`` query heads a key/value head. A
  window layer's query ``i`` sees key ``j`` where ``0 <= i - j <
  sliding_window``, and its softmax has one more term a query head, the
  learned *sink* ``sink_h``: ``p_ij = exp(s_ij) / (sum_j' exp(s_ij') +
  exp(sink_h))``, which takes probability and adds no value. ``x += W_o o``.
- **Feed-forward.** A dense SwiGLU where ``moe_layers`` says 0; elsewhere
  :func:`ray_tpu.models.latent_moe.moe_ffn` with no shared expert: a float32
  sigmoid router over all experts of the model, the ``experts_per_token``
  largest of ``s + b``, weights ``s / sum(s)``, the experts held here on the
  picks that land on them.

The cache is :mod:`ray_tpu.models.paged`'s second table kind that keeps a
window, ``{"full": {"k", "v"}, "window": {"k", "v"}}``, **each part with its
kind's own head count**: keys ``[layers of the kind, blocks of the part, KH of
the kind, block, key_lanes]`` and values ``[..., Dv]``. ``key_lanes`` is the
key's 192 in whole lane tiles, 256, zeros behind the key: the decode kernel
copies whole tiles of a row only (``ops/paged_attention.py``), and the
compiler lays a minor dimension of 192 out in 256 lanes whatever it is told
(PERF.md section 6, PR 48). The value scale multiplies ``v`` before it is
written, so the cache holds scaled rows. What the attention functions need of
each kind is said once, in :func:`attention_kinds`.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from ray_tpu.models import latent_moe, paged
from ray_tpu.models.common import _rms_norm, stage
from ray_tpu.models.latent_moe import ffn, final_logits, outputs

Params = dict
_F32 = jnp.float32

FULL, WINDOW = 0, 1  # entries of hybrid_layer_pattern
PARTS = ("full", "window")  # the cache's part of each
# Layer 0 full, 1-4 window, 5 full, then five window layers to one full one.
PUBLISHED_LAYER_PATTERN = (FULL, *(WINDOW,) * 4, *((FULL, *(WINDOW,) * 5) * 7), FULL)
PUBLISHED_MOE_LAYERS = (0, *(1,) * 47)  # moe_layer_freq: layer 0 dense


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    """Published key meanings (``config.json``); defaults are the published
    MiMo-V2.5 sizes, uncut."""

    family: ClassVar[str] = "mimo_v2"

    vocab_size: int = 152576  # rows of the embedding and the head held here
    d_model: int = 4096
    layer_pattern: tuple = PUBLISHED_LAYER_PATTERN  # hybrid_layer_pattern of the layers held, in order
    moe_layers: tuple = PUBLISHED_MOE_LAYERS  # moe_layer_freq of the same layers: 1 experts, 0 dense
    # Attention
    n_head: int = 64  # of both kinds
    n_kv_head: int = 4  # a full layer's
    swa_n_kv_head: int = 8  # a window layer's
    head_dim: int = 192  # of a query and a key, both kinds
    v_head_dim: int = 128
    rotary_dim: int = 64  # partial_rotary_factor of head_dim: a head's first lanes, rotated
    sliding_window: int = 128
    rope_theta: float = 1e7  # a full layer's
    swa_rope_theta: float = 1e4  # a window layer's
    value_scale: float = 0.707  # attention_value_scale
    full_sink: bool = False  # add_full_attention_sink_bias
    swa_sink: bool = True  # add_swa_attention_sink_bias
    # Feed-forward
    d_ff: int = 16384  # the dense layers'
    moe_d_ff: int = 2048
    n_experts: int = 256  # the router's width: all routed experts of the model
    experts_held: int = 256  # of them, the ones whose weights are here ...
    expert_offset: int = 0  # ... starting from this one
    experts_per_token: int = 8
    n_group: int = 1  # the grouped top-k is a plain one
    topk_group: int = 1
    routed_scaling: float = 1.0  # routed_scaling_factor null
    renormalize: bool = True  # norm_topk_prob
    hidden_act: str = "silu"
    # Serving. The last two size the window part of the cache where the caller
    # names no count of blocks (init_pool): the slots of the deployment, and
    # the tokens of its longest prefill program.
    max_seq: int = 4096
    window_slots: int = 16
    prefill_span: int = 2048
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # Ids whose columns of the head draw_params leaves at zero (afmoe's reason).
    silent_ids: tuple = ()

    def __post_init__(self):
        assert set(self.layer_pattern) <= {FULL, WINDOW}, self.layer_pattern
        assert len(self.moe_layers) == len(self.layer_pattern) and any(self.moe_layers)
        assert 0 <= self.expert_offset
        assert self.expert_offset + self.experts_held <= self.n_experts
        assert self.n_head % self.n_kv_head == 0 and self.n_head % self.swa_n_kv_head == 0
        assert self.rotary_dim % 2 == 0 and self.rotary_dim <= self.head_dim

    @property
    def n_layer(self) -> int:
        return len(self.layer_pattern)

    def is_moe(self, layer: int) -> bool:
        """Layers numbered from 1, as :mod:`latent_moe` counts them."""
        return bool(self.moe_layers[layer - 1])

    @property
    def n_moe_layers(self) -> int:
        return sum(map(bool, self.moe_layers))

    def layers_of(self, kind: int) -> int:
        return self.layer_pattern.count(kind)

    @property
    def key_lanes(self) -> int:
        """The key pool's row width: a key wider than a lane tile in whole
        tiles (192 -> 256), a narrower one as it is (the tests' sizes, which
        no kernel takes anyway)."""
        return latent_moe.whole_tiles(self.head_dim) if self.head_dim > 128 else self.head_dim

    @staticmethod
    def tiny(
        layer_pattern=(FULL, WINDOW, WINDOW, FULL, WINDOW), moe_layers=None, vocab_size: int = 512,
        max_seq: int = 256, experts_held: int = 8, expert_offset: int = 0, **kw,
    ) -> "MimoV2Config":
        """A CPU-test size with the published ratios: keys 1.5 times as wide
        as values, a third of a key rotated, twice the key/value heads in a
        window layer, a dense layer and then expert layers, a window of 6
        (shorter than a chunk, no multiple of a block of 4)."""
        moe_layers = (0, *(1,) * (len(layer_pattern) - 1)) if moe_layers is None else moe_layers
        return MimoV2Config(**{**dict(
            vocab_size=vocab_size, d_model=64, layer_pattern=tuple(layer_pattern),
            moe_layers=tuple(moe_layers), n_head=4, n_kv_head=1, swa_n_kv_head=2, head_dim=24,
            v_head_dim=16, rotary_dim=8, sliding_window=6, d_ff=128, moe_d_ff=32, n_experts=8,
            experts_held=experts_held, expert_offset=expert_offset, experts_per_token=2,
            max_seq=max_seq, window_slots=4, prefill_span=8, dtype=jnp.float32,
            param_dtype=jnp.float32,
        ), **kw})


def attention_kinds(cfg: MimoV2Config) -> tuple:
    """What :mod:`paged`'s attention functions need of a full layer and of a
    window layer, in the order of ``PARTS``: the kinds differ in key/value
    heads, window and sink, and share the widths. Their calls of the decode
    kernel are ``paged_decode_attention_full`` / ``_window`` in a trace."""
    size = jnp.dtype(cfg.dtype).itemsize
    lanes = cfg.key_lanes if cfg.key_lanes != cfg.head_dim else None
    return (
        paged.AttentionKind(
            cfg.n_kv_head, cfg.head_dim, cfg.v_head_dim, size, None, cfg.full_sink, lanes, "full",
            cfg.layers_of(FULL)),
        paged.AttentionKind(
            cfg.swa_n_kv_head, cfg.head_dim, cfg.v_head_dim, size, cfg.sliding_window,
            cfg.swa_sink, lanes, "window", cfg.layers_of(WINDOW)),
    )


def cache(cfg: MimoV2Config) -> paged.Cache:
    """Keys and values per head in blocks under a table a layer kind: a full
    layer keeps every position, a window layer the window; each kind with its
    own shapes."""
    return paged.Cache(
        retention=(None, cfg.sliding_window), kinds=attention_kinds(cfg), prefill_in_place=True
    )


# ---------------------------------------------------------------------------
# Parameters

# What init_params balances the routers' selection bias over (balance_routers):
# rounds, the tokens of a round's sequence, and the ids its tokens are drawn from.
_BALANCE_ROUNDS, _BALANCE_TOKENS = 96, 4096
_BALANCE_TEXT_IDS = (32, 127)
# The deviation draw_params gives a score: 0.02^2 x 4,096, the published size's.
_SCORE_DEV = 1.6384
# The share of a window row's probability that draw_params aims a sink at, in
# odds against the keys, and the deviation of its draw over the heads.
_SINK_ODDS, _SINK_STD = 1 / 3, 0.5


@functools.partial(jax.jit, static_argnames="cfg")
def init_params(key: jax.Array, cfg: MimoV2Config) -> Params:
    """Random weights (:func:`draw_params`) with each router's selection bias
    balanced as a served checkpoint's is (:func:`latent_moe.balance_routers`,
    over sequences of printable bytes, one table for both kinds of layer and a
    window part as large as the full one), as ``afmoe.init_params`` balances
    its own and for its reason. One program, which the compile cache keeps."""
    key, sub = jax.random.split(key)
    lo, hi = _BALANCE_TEXT_IDS
    hi = min(hi, cfg.vocab_size)

    def prefill_of_text(params, tokens, *args, **kw):
        return paged_prefill(params, lo + tokens % (hi - lo), *args, **kw)

    return latent_moe.balance_routers(
        draw_params(key, cfg), sub, cfg, _BALANCE_ROUNDS, min(_BALANCE_TOKENS, cfg.max_seq),
        lambda c, n, bs, slots: init_pool(c, n, bs, slots, window_blocks=n), prefill_of_text,
    )


def draw_params(key: jax.Array, cfg: MimoV2Config) -> Params:
    """Random weights, drawn tensor by tensor in the parameter dtype: N(0,
    ``std``) with ``std^2 d_model = _SCORE_DEV`` (0.02 at the published 4,096),
    norms one, the router in float32 with unit-variance logits and a zero
    selection bias. A normed hidden state then gives scores ``q.k Dk^-1/2`` a
    deviation of ``_SCORE_DEV`` at every size (a query attends some tens of
    keys of its own, the regime ``afmoe`` sets by hand).

    **The sink** of a layer whose kind has one, float32 [H], is drawn so that
    it matters: ``ln(window) + _SCORE_DEV^2 / 2 + ln(odds) + N(0, 0.5)``, where the
    first two terms are the logarithm of what a full window's keys sum to on
    average (``window`` terms of ``exp`` of a normal score of deviation
    ``_SCORE_DEV``) and ``odds`` is 1/3: the sink then takes about a quarter of a
    window row's probability, between a tenth and a half by the head (the
    reference's ``sink_share`` reads it; the tests hold it to that range). A
    sink near ``-inf`` would let every check pass without it.
    ``silent_ids``: those columns of the head are zero."""
    pd = cfg.param_dtype
    D, H, Dk, Dv = cfg.d_model, cfg.n_head, cfg.head_dim, cfg.v_head_dim
    E, Fm = cfg.experts_held, cfg.moe_d_ff
    keys = iter(jax.random.split(key, 16 * cfg.n_layer + 8))

    def w(shape, s=(_SCORE_DEV / D) ** 0.5, dtype=pd):
        return jax.random.normal(next(keys), shape, dtype) * jnp.asarray(s, dtype)

    sink_mean = math.log(cfg.sliding_window) + _SCORE_DEV**2 / 2 + math.log(_SINK_ODDS)

    def attention(kind: paged.AttentionKind):
        KH = kind.kv_heads
        p = {"wq": w((D, H * Dk)), "wk": w((D, KH * Dk)), "wv": w((D, KH * Dv)), "wo": w((H * Dv, D))}
        if kind.sink:
            p["sink"] = sink_mean + w((H,), _SINK_STD, _F32)
        return p

    def dense():
        return {"w_gate": w((D, cfg.d_ff)), "w_up": w((D, cfg.d_ff)), "w_down": w((cfg.d_ff, D))}

    def moe():
        return {
            "router": w((D, cfg.n_experts), D**-0.5, _F32),
            "router_bias": jnp.zeros((cfg.n_experts,), _F32),
            "e_gate": w((E, D, Fm)), "e_up": w((E, D, Fm)), "e_down": w((E, Fm, D)),
        }

    kinds = attention_kinds(cfg)
    layers = [
        {
            "in_norm": jnp.ones((D,), pd), "mlp_norm": jnp.ones((D,), pd), **attention(kinds[kind]),
            **(moe() if cfg.is_moe(i) else dense()),
        }
        for i, kind in enumerate(cfg.layer_pattern, 1)
    ]
    wte, head = w((cfg.vocab_size, D)), w((D, cfg.vocab_size))
    if cfg.silent_ids:
        head = head.at[:, jnp.asarray(cfg.silent_ids)].set(0)
    return {"wte": wte, "layers": layers, "final_norm": jnp.ones((D,), pd), "lm_head": head}


# ---------------------------------------------------------------------------
# The layer


def _ropes(cfg: MimoV2Config, positions) -> tuple:
    """``(cos, sin)`` [..., rotary_dim / 2] float32 of ``positions`` [...], a
    layer kind: a full layer's base, a window layer's."""
    half = cfg.rotary_dim // 2
    exponent = -jnp.arange(half, dtype=_F32) / half

    def tables(theta):
        angles = positions.astype(_F32)[..., None] * theta**exponent
        return jnp.cos(angles), jnp.sin(angles)

    return tables(cfg.rope_theta), tables(cfg.swa_rope_theta)


def _rotate(t, rope):
    """``t`` [..., heads, Dk] with its first ``rotary_dim`` lanes rotated in
    halves (``i`` with ``i + rotary_dim / 2``, the published ``rotate_half``)
    by the angles of ``rope`` [..., rotary_dim / 2]; the other lanes as they
    are."""
    cos, sin = (a[..., None, :] for a in rope)  # one angle for every head
    half = cos.shape[-1]
    t32 = t.astype(_F32)
    t1, t2, rest = t32[..., :half], t32[..., half : 2 * half], t32[..., 2 * half :]
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos, rest], axis=-1).astype(t.dtype)


@stage("attn_proj")
def _qkv(a, p, cfg: MimoV2Config, kind: paged.AttentionKind, rope, *, held: bool = False):
    """``a`` [..., D] normed -> ``(q [..., KH, group, Dk], k [..., KH,
    key_lanes], v [..., KH, Dv])`` of a layer of ``kind``: ``q`` and ``k``
    rotated in their first lanes, ``k`` with zeros up to the pool's row, ``v``
    scaled. ``held`` (a chunk's rows): the query projection is one value
    behind an optimization barrier. Without it a v5e's compiler forms a
    window layer's ``[2048, 4096] x [4096, 12288]`` product twice, once for
    the lanes the rope rotates and once for the others (206 GFLOP each time,
    five layers of the benchmark's seven: PERF.md section 6, PR 50)."""
    dt = cfg.dtype
    H, KH, Dk, Dv = cfg.n_head, kind.kv_heads, cfg.head_dim, cfg.v_head_dim
    lead = a.shape[:-1]
    q = a @ p["wq"].astype(dt)
    q = _rotate((jax.lax.optimization_barrier(q) if held else q).reshape(*lead, H, Dk), rope)
    k = _rotate((a @ p["wk"].astype(dt)).reshape(*lead, KH, Dk), rope)
    v = (a @ p["wv"].astype(dt)).reshape(*lead, KH, Dv)
    v = (v.astype(_F32) * cfg.value_scale).astype(dt)
    if cfg.key_lanes > Dk:
        k = jnp.pad(k, ((0, 0),) * (k.ndim - 1) + ((0, cfg.key_lanes - Dk),))
    return q.reshape(*lead, KH, H // KH, Dk), k, v


def _sink(p, kind: paged.AttentionKind):
    """The layer's sinks as the attention functions take them, [KH, group],
    or None for a kind without."""
    return p["sink"].reshape(kind.kv_heads, -1) if kind.sink else None


@stage("attn_proj")
def _out(x, o, p, cfg: MimoV2Config):
    """``x + W_o o``: ``o`` [..., KH, group, Dv]."""
    o = o.reshape(*o.shape[:-3], cfg.n_head * cfg.v_head_dim)
    return x + o @ p["wo"].astype(cfg.dtype)


def _layers(params, cfg: MimoV2Config):
    """(layer number from 1, its parameters, its kind (``FULL`` / ``WINDOW``:
    an index into ``PARTS``, the ropes and the attention kinds), its index
    among the layers of its kind)."""
    seen = [0, 0]
    for i, (kind, p) in enumerate(zip(cfg.layer_pattern, params["layers"])):
        yield i + 1, p, kind, seen[kind]
        seen[kind] += 1


def span_fields(cfg: MimoV2Config, counts, tokens: int, slots: int, decode=None) -> dict:
    """:func:`ray_tpu.models.latent_moe.span_fields` of the expert layers
    (``slots`` and ``decode`` name nothing here: no state is stepped, and the
    rows of keys and values a step needs and reads, by kind, are the engine's
    own count off the positions and the window)."""
    return latent_moe.span_fields(cfg, counts, tokens)


# ---------------------------------------------------------------------------
# The paged programs (models/paged.py dispatches here by cfg.family)


def init_pool(cfg: MimoV2Config, num_blocks: int, block_size: int, slots=None, window_blocks=None):
    """The zeroed cache, a part a layer kind, each with its kind's key/value
    heads: keys ``[layers, blocks, KH, block, key_lanes]``, values ``[...,
    Dv]``. ``num_blocks`` sizes the part of the layers that keep everything.
    The window part has ``window_blocks`` blocks (the engine's count), or,
    where none is named, what ``slots`` sequences (None: ``cfg.window_slots``)
    hold at most, each :func:`paged.window_blocks_a_slot` of the window and
    ``cfg.prefill_span``, and the scratch block."""
    if window_blocks is None:
        slots = cfg.window_slots if slots is None else slots
        window_blocks = 1 + slots * paged.window_blocks_a_slot(
            cfg.sliding_window, cfg.prefill_span, block_size
        )

    def part(kind, blocks):
        lead = (cfg.layers_of(kind), blocks, attention_kinds(cfg)[kind].kv_heads, block_size)
        return {
            "k": jnp.zeros((*lead, cfg.key_lanes), cfg.dtype),
            "v": jnp.zeros((*lead, cfg.v_head_dim), cfg.dtype),
        }

    return {"full": part(FULL, num_blocks), "window": part(WINDOW, window_blocks)}


def _by_kind(tables):
    """``(the full kind's table, the window kind's)`` of ``tables`` [..., 2,
    W], or of one table [..., W] for both."""
    return tables[..., 0, :], tables[..., 1, :]


def paged_prefill(
    params, tokens, length, start, table, pool, cfg: MimoV2Config, *,
    block_size: int, slot=None, with_picks: bool = False,
):
    """Prefill positions [start, start + T) of one sequence; operands as
    :func:`ray_tpu.models.paged.paged_prefill` (``slot`` names nothing here),
    ``table`` [2, W] a kind, or [W] for both. ``start > 0`` continues a
    sequence whose earlier rows are in the pool under the tables: a later
    chunk. Each layer writes the chunk's keys and values, then reads its part
    a stretch of the table at a time (:func:`paged.prefill_attention`): a full
    layer from position 0, a window layer from the block that holds ``start -
    sliding_window + 1`` and from its sink. Returns ``(pool, last_logits
    [vocab] float32, counts int32 [expert layers, 2])``, and with
    ``with_picks`` the chosen experts [expert layers, T, k]."""
    T = tokens.shape[1]
    tables = _by_kind(table if table.ndim == 2 else jnp.stack([table, table]))
    kinds = attention_kinds(cfg)
    pos = start + jnp.arange(T, dtype=jnp.int32)
    valid = jnp.arange(T) < length
    with stage("attn_proj"):
        ropes = _ropes(cfg, pos)
    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[tokens[0]]
    pool = {part: dict(kv) for part, kv in pool.items()}
    seen: list = []
    for layer, p, kind, l in _layers(params, cfg):
        with stage("attn_proj"):
            a = _rms_norm(x, p["in_norm"], cfg.rms_eps)
        q, k, v = _qkv(a, p, cfg, kinds[kind], ropes[kind], held=True)
        tab, kv = tables[kind], pool[PARTS[kind]]
        kv["k"] = paged._write_blocks(kv["k"], l, tab, start, k, block_size)
        kv["v"] = paged._write_blocks(kv["v"], l, tab, start, v, block_size)
        o = paged.prefill_attention(
            q, kv["k"], kv["v"], l, tab, pos, start + length, block_size=block_size,
            window=kinds[kind].window, sink=_sink(p, kinds[kind]), name=kinds[kind].name,
        )
        x = ffn(_out(x, o, p, cfg), p, cfg, layer, valid, seen)
    with stage("embed_head"):
        last = jax.lax.dynamic_index_in_dim(x, (length - 1).astype(jnp.int32), 0, keepdims=False)
    logits = final_logits(params, last[None], cfg)[0]
    return outputs(pool, logits, seen, with_picks)


def paged_decode(
    params, last_tokens, positions, tables, pool, cfg: MimoV2Config, *,
    block_size: int, live=None, with_picks: bool = False, interpret: bool = False,
):
    """One token a slot; operands as :func:`ray_tpu.models.paged.paged_decode`,
    ``tables`` [B, 2, W] a kind, or [B, W] for both, plus ``live`` [B] bool: a
    slot that is not live (free, or still prefilling in chunks) is routed to
    no expert; its logits mean nothing and its key and value go where its
    tables point (the scratch block, or the next chunk's first position).
    Each layer writes the step's key and value, then attends positions [0,
    position] of every slot, a window layer the last ``sliding_window`` of
    them beside its sink: over the live blocks in place or over the gathered
    table, each kind by its own shapes
    (:func:`ray_tpu.models.paged.decode_attention`; ``interpret`` runs its
    kernel in the Pallas interpreter: the tests). Returns ``(pool, logits [B,
    vocab] float32, counts)``."""
    B = last_tokens.shape[0]
    tables = _by_kind(tables if tables.ndim == 3 else jnp.stack([tables, tables], axis=1))
    kinds = attention_kinds(cfg)
    attend = [paged.decode_attention(kind, block_size, None, interpret) for kind in kinds]
    with stage("pool_write"):
        rows = jnp.arange(B)
        offs = positions % block_size
    with stage("attn_core"):
        lengths = positions + 1  # the step's own key is attended
    with stage("attn_proj"):
        ropes = _ropes(cfg, positions)
    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[last_tokens]
    pool = {part: dict(kv) for part, kv in pool.items()}
    seen: list = []
    for layer, p, kind, l in _layers(params, cfg):
        with stage("attn_proj"):
            a = _rms_norm(x, p["in_norm"], cfg.rms_eps)
        q, k, v = _qkv(a, p, cfg, kinds[kind], ropes[kind])
        tab, kv = tables[kind], pool[PARTS[kind]]
        with stage("pool_write"):
            bids = tab[rows, positions // block_size]
        kv["k"] = paged._write(kv["k"], l, bids, offs, k)
        kv["v"] = paged._write(kv["v"], l, bids, offs, v)
        with stage("attn_core"):
            o = attend[kind](
                q, kv["k"], kv["v"], jnp.asarray(l, jnp.int32), tab, lengths, _sink(p, kinds[kind])
            )
        x = ffn(_out(x, o, p, cfg), p, cfg, layer, live, seen)
    return outputs(pool, final_logits(params, x, cfg), seen, with_picks)
