"""AFMoE (``model_type: afmoe``; arcee-ai Trinity): a decoder whose attention
layers are of two kinds, three that attend a sliding window to one that attends
everything, with gated attention, sandwich norms and a routed mixture of
experts behind a few dense layers. Sixth model family of the serving tier, and
the first whose cache has a part that gives blocks back while a request runs.

The layer (four norms, ``x += Norm(Sublayer(Norm(x)))`` twice):

- ``x0 = E[token] sqrt(d_model)`` (``mup_enabled``).
- **Attention.** ``a = RMSNorm(x)``; ``q = W_q a`` [H, Dh], ``k = W_k a``, ``v =
  W_v a`` [KH, Dh], ``g = W_g a`` [H Dh]; ``q`` and ``k`` each through an
  RMSNorm over a head's ``Dh``. A *sliding* layer rotates ``q`` and ``k`` by
  their position (the whole head, halves ``(i, i + Dh/2)`` paired, plain
  ``rope_theta``); a *full* layer does not rotate at all. Scores ``q.k
  Dh^-1/2``, float32 softmax, ``H / KH`` query heads a key/value head; causal,
  and in a sliding layer key ``j`` is seen by query ``i`` only where ``0 <= i -
  j < sliding_window``. ``attn = W_o (o sigmoid(g))``; ``x += RMSNorm(attn)``.
- **Feed-forward.** ``m = RMSNorm(x)``; a dense SwiGLU in the leading layers;
  elsewhere :func:`ray_tpu.models.latent_moe.moe_ffn`: a float32 sigmoid router
  over all experts of the model, the ``experts_per_token`` largest of ``s + b``
  (``b`` a selection bias), weights ``s / sum(s) route_scale``, the experts
  held here on the picks that land on them, plus one shared expert; ``x +=
  RMSNorm(f)``.
- Final RMSNorm; untied head.

The cache has a second table kind that keeps a window
(:mod:`ray_tpu.models.paged`, "What a pool is made of"): ``{"full": {"k", "v"},
"window": {"k", "v"}}``, each ``[layers of the kind, blocks of the part, KH,
block, Dh]``, under a block table a kind
(``tables [..., 2, W]``; one table ``[..., W]`` serves both where nothing was
given back). :func:`cache` tells the engine that the second kind keeps
``sliding_window`` positions only: its blocks behind the window go back to the
free list while the request runs, and its table points at the scratch block
there. Decode attends through :func:`paged.decode_attention` (the kernel's
walk from the block that holds ``length - window`` on a TPU, the gather under
the same mask elsewhere), prefill through :func:`paged.prefill_attention`, a
stretch of the table at a time from the block that holds the window's first
column.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from ray_tpu.models import latent_moe, paged
from ray_tpu.models.latent_moe import final_logits, moe_ffn, outputs
from ray_tpu.models.common import _rms_norm, stage

Params = dict
_F32 = jnp.float32

SLIDING, FULL = "sliding_attention", "full_attention"
PUBLISHED_LAYER_TYPES = (SLIDING, SLIDING, SLIDING, FULL) * 15


def cache(cfg) -> paged.Cache:
    """Keys and values per head in blocks under a table a layer kind: a full
    layer keeps every position, a sliding layer the window."""
    return paged.Cache(retention=(None, cfg.sliding_window), prefill_in_place=True)


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """Published key meanings (``config.json``); defaults are the published
    Trinity-Large-Preview sizes, uncut."""

    family: ClassVar[str] = "afmoe"

    vocab_size: int = 200192  # rows of the embedding and the head held here
    d_model: int = 3072
    layer_types: tuple = PUBLISHED_LAYER_TYPES  # of the layers held here, in order
    n_dense: int = 6  # num_dense_layers: of the layers held, the leading ones without experts
    # Attention
    n_head: int = 48
    n_kv_head: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    rope_theta: float = 10000.0
    # Feed-forward
    d_ff: int = 12288  # the dense layers'
    moe_d_ff: int = 3072
    n_experts: int = 256  # the router's width: all routed experts of the model
    experts_held: int = 256  # of them, the ones whose weights are here ...
    expert_offset: int = 0  # ... starting from this one
    experts_per_token: int = 4
    n_shared_experts: int = 1
    n_group: int = 1  # the grouped top-k is a plain one
    topk_group: int = 1
    routed_scaling: float = 2.448  # route_scale
    renormalize: bool = True  # route_norm
    hidden_act: str = "silu"
    mup: bool = True  # mup_enabled: the embedding times sqrt(d_model)
    # Serving. The last two size the window part of the cache where the caller
    # names no count of blocks (init_pool): the slots of the deployment, and
    # the tokens of its longest prefill program.
    max_seq: int = 4096
    window_slots: int = 16
    prefill_span: int = 2048
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # Ids whose columns of the head draw_params leaves at zero (their logit is
    # 0, far under the largest of the others): a random model would end an
    # answer at the tokenizer's EOS once in vocab_size tokens, by the seed.
    silent_ids: tuple = ()

    def __post_init__(self):
        assert set(self.layer_types) <= {SLIDING, FULL}, self.layer_types
        assert 0 <= self.n_dense < len(self.layer_types)  # an expert layer at least
        assert 0 <= self.expert_offset
        assert self.expert_offset + self.experts_held <= self.n_experts
        assert self.n_head % self.n_kv_head == 0 and self.head_dim % 2 == 0

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    def is_moe(self, layer: int) -> bool:
        """Layers numbered from 1, as :mod:`latent_moe` counts them."""
        return layer > self.n_dense

    @property
    def n_moe_layers(self) -> int:
        return self.n_layer - self.n_dense

    def layers_of(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @staticmethod
    def tiny(
        layer_types=(SLIDING, SLIDING, FULL, SLIDING), n_dense: int = 1, vocab_size: int = 512,
        max_seq: int = 256, experts_held: int = 8, expert_offset: int = 0, **kw,
    ) -> "AfmoeConfig":
        """A CPU-test size: a dense layer, then expert layers, both kinds of
        attention, a window of 8."""
        return AfmoeConfig(**{**dict(
            vocab_size=vocab_size, d_model=64, layer_types=tuple(layer_types), n_dense=n_dense,
            n_head=4, n_kv_head=2, head_dim=16, sliding_window=8, d_ff=128, moe_d_ff=32,
            n_experts=8, experts_held=experts_held, expert_offset=expert_offset,
            experts_per_token=2, max_seq=max_seq, window_slots=4, prefill_span=8,
            dtype=jnp.float32, param_dtype=jnp.float32,
        ), **kw})


# ---------------------------------------------------------------------------
# Parameters

# What init_params balances the routers' selection bias over (balance_routers):
# rounds, the tokens of a round's sequence, and the ids its tokens are drawn from.
_BALANCE_ROUNDS, _BALANCE_TOKENS = 96, 4096
_BALANCE_TEXT_IDS = (32, 127)


@functools.partial(jax.jit, static_argnames="cfg")
def init_params(key: jax.Array, cfg: AfmoeConfig) -> Params:
    """Random weights (:func:`draw_params`) with each router's selection bias
    balanced as a served checkpoint's is (:func:`latent_moe.balance_routers`,
    over one table for both kinds of layer and a window part as large as the
    full one: nothing is given back in a prompt of a window's length). One
    program, which the compile cache keeps.

    The bias is balanced over what is served: sequences of printable bytes
    (ids 32-126), a window long. What the ids of a context have in common
    reaches every router alike, and the ids of the whole vocabulary have
    something else in common: a bias balanced there left the experts a chip
    holds with 9-16% of the served text's picks by the seed, where 32 of 256
    are 12.5% (PERF.md section 6, PR 40)."""
    key, sub = jax.random.split(key)
    lo, hi = _BALANCE_TEXT_IDS
    hi = min(hi, cfg.vocab_size)

    def prefill_of_text(params, tokens, *args, **kw):
        return paged_prefill(params, lo + tokens % (hi - lo), *args, **kw)

    return latent_moe.balance_routers(
        draw_params(key, cfg), sub, cfg, _BALANCE_ROUNDS, min(_BALANCE_TOKENS, cfg.max_seq),
        lambda c, n, bs, slots: init_pool(c, n, bs, slots, window_blocks=n), prefill_of_text,
    )


# The weights of the norms over a head's ``q`` and ``k`` as draw_params sets them.
_QK_NORM_GAIN = 2**0.5


def draw_params(key: jax.Array, cfg: AfmoeConfig) -> Params:
    """Random weights, drawn tensor by tensor in the parameter dtype: no
    float32 copy of an expert stack is ever live. N(0, 0.02) (the published
    initialiser scales by depth; behind a norm of its own a sublayer's scale
    does not reach the forward); the four norms of a layer one; the router in
    float32 with unit-variance logits and a zero selection bias.

    The norms over a head's ``q`` and ``k`` are ``_QK_NORM_GAIN`` each, so that
    a score ``q.k Dh^-1/2`` has a deviation of 2 and not of 1. At one a query
    weighs the thousands of keys of its window nearly alike and attention
    returns the context's mean whatever the query: every position of a request
    shares two fifths of its hidden state, a greedy answer cycles through a
    few tokens, a slot picks the experts it picked the step before (6-47% of
    its picks by the seed, where chance repeats 1.6%), and how many experts a
    decode step touches, which is its time, is drawn once a request and not
    once a token. At 2 a query attends some tens of keys of its own, 3-4% of
    picks repeat and a step touches 94-96% of what chance gives on every seed;
    at 2.5 and 3.5 no more evenly, and bfloat16 then flips more near-tie picks
    against the float32 reference (PERF.md section 6, PR 40). These are
    values of parameters the published model has; no term is added.
    ``silent_ids``: those columns of the head are zero."""
    pd = cfg.param_dtype
    D, H, KH, Dh = cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    E, Fm = cfg.experts_held, cfg.moe_d_ff
    std = 0.02
    keys = iter(jax.random.split(key, 16 * cfg.n_layer + 8))

    def w(shape, s=std, dtype=pd):
        return jax.random.normal(next(keys), shape, dtype) * jnp.asarray(s, dtype)

    def attention():
        return {
            "wq": w((D, H * Dh)), "wk": w((D, KH * Dh)), "wv": w((D, KH * Dh)),
            "wg": w((D, H * Dh)), "wo": w((H * Dh, D)),
            "q_norm": jnp.full((Dh,), _QK_NORM_GAIN, pd), "k_norm": jnp.full((Dh,), _QK_NORM_GAIN, pd),
        }

    def dense():
        return {"w_gate": w((D, cfg.d_ff)), "w_up": w((D, cfg.d_ff)), "w_down": w((cfg.d_ff, D))}

    def moe():
        Fs = Fm * cfg.n_shared_experts
        return {
            "router": w((D, cfg.n_experts), D**-0.5, _F32),
            "router_bias": jnp.zeros((cfg.n_experts,), _F32),
            "e_gate": w((E, D, Fm)), "e_up": w((E, D, Fm)), "e_down": w((E, Fm, D)),
            "s_gate": w((D, Fs)), "s_up": w((D, Fs)), "s_down": w((Fs, D)),
        }

    norms = ("in_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")
    layers = [
        {
            **{n: jnp.ones((D,), pd) for n in norms}, **attention(),
            **(moe() if cfg.is_moe(i) else dense()),
        }
        for i in range(1, cfg.n_layer + 1)
    ]
    wte, head = w((cfg.vocab_size, D)), w((D, cfg.vocab_size))
    if cfg.silent_ids:
        head = head.at[:, jnp.asarray(cfg.silent_ids)].set(0)
    return {"wte": wte, "layers": layers, "final_norm": jnp.ones((D,), pd), "lm_head": head}


# ---------------------------------------------------------------------------
# The layer


def _rope(cfg: AfmoeConfig, positions):
    """``(cos, sin)`` [..., Dh / 2] float32 of ``positions`` [...]."""
    half = cfg.head_dim // 2
    freqs = cfg.rope_theta ** (-jnp.arange(half, dtype=_F32) / half)
    angles = positions.astype(_F32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def _rotate(t, rope):
    """``t`` [..., heads, Dh] rotated in halves (``i`` with ``i + Dh/2``, the
    published ``rotate_half``) by the angles of ``rope`` [..., Dh / 2]."""
    cos, sin = (a[..., None, :] for a in rope)  # one angle for every head
    t1, t2 = jnp.split(t.astype(_F32), 2, axis=-1)
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], axis=-1).astype(t.dtype)


@stage("attn_proj")
def _qkvg(a, p, cfg: AfmoeConfig, rope):
    """``a`` [..., D] normed -> ``(q [..., KH, group, Dh], k, v [..., KH, Dh], g
    [..., H Dh])``: ``q`` and ``k`` normed a head, and rotated where ``rope``
    is given (a sliding layer)."""
    dt = cfg.dtype
    H, KH, Dh = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    lead = a.shape[:-1]
    q = _rms_norm((a @ p["wq"].astype(dt)).reshape(*lead, H, Dh), p["q_norm"], cfg.rms_eps)
    k = _rms_norm((a @ p["wk"].astype(dt)).reshape(*lead, KH, Dh), p["k_norm"], cfg.rms_eps)
    v = (a @ p["wv"].astype(dt)).reshape(*lead, KH, Dh)
    if rope is not None:
        q, k = _rotate(q, rope), _rotate(k, rope)
    return q.reshape(*lead, KH, H // KH, Dh), k, v, a @ p["wg"].astype(dt)


@stage("attn_proj")
def _gated_out(x, o, g, p, cfg: AfmoeConfig):
    """``x + RMSNorm(W_o (o sigmoid(g)))``: ``o`` [..., KH, group, Dh]."""
    o = o.reshape(g.shape)
    gated = (o.astype(_F32) * jax.nn.sigmoid(g.astype(_F32))).astype(cfg.dtype)
    return x + _rms_norm(gated @ p["wo"].astype(cfg.dtype), p["post_attn_norm"], cfg.rms_eps)


def _ffn(x, p, cfg: AfmoeConfig, layer: int, valid, seen: list):
    """The feed-forward sublayer between its two norms, with its residual;
    an expert layer's counts and picks are appended to ``seen``."""
    dt = cfg.dtype
    around = "experts" if cfg.is_moe(layer) else "mlp"  # the sandwich norms and the residual
    with stage(around):
        m = _rms_norm(x, p["pre_mlp_norm"], cfg.rms_eps)
    if cfg.is_moe(layer):
        f, counts, picks = moe_ffn(m, p, cfg, valid)
        seen.append((counts, picks))
    else:
        with stage("mlp"):
            f = (jax.nn.silu(m @ p["w_gate"].astype(dt)) * (m @ p["w_up"].astype(dt))) @ p["w_down"].astype(dt)
    with stage(around):
        return x + _rms_norm(f, p["post_mlp_norm"], cfg.rms_eps)


def _layers(params, cfg: AfmoeConfig):
    """(layer number from 1, its parameters, its part of the cache, its index
    among the layers of its kind, its window or None)."""
    seen = {FULL: 0, SLIDING: 0}
    for i, (kind, p) in enumerate(zip(cfg.layer_types, params["layers"])):
        part, window = ("window", cfg.sliding_window) if kind == SLIDING else ("full", None)
        yield i + 1, p, part, seen[kind], window
        seen[kind] += 1


def _by_kind(tables):
    """``{"full": table, "window": table}`` of ``tables`` [..., 2, W] (a caller
    with one table for both kinds stacks it twice first)."""
    return {"full": tables[..., 0, :], "window": tables[..., 1, :]}


def span_fields(cfg: AfmoeConfig, counts, tokens: int, slots: int, decode=None) -> dict:
    """:func:`ray_tpu.models.latent_moe.span_fields` of the expert layers
    (``slots`` and ``decode`` name nothing here: no state is stepped, and the
    rows of keys and values a step needs and reads, by kind, are the engine's
    own count off the positions and the window)."""
    return latent_moe.span_fields(cfg, counts, tokens)


# ---------------------------------------------------------------------------
# The paged programs (models/paged.py dispatches here by cfg.family)


def init_pool(cfg: AfmoeConfig, num_blocks: int, block_size: int, slots=None, window_blocks=None):
    """The zeroed cache, a part a layer kind. ``num_blocks`` sizes the part of
    the layers that keep everything. The window part has ``window_blocks``
    blocks (the engine's count), or, where none is named, what ``slots``
    sequences (None: ``cfg.window_slots``) hold at most, each
    :func:`paged.window_blocks_a_slot` of the window and ``cfg.prefill_span``,
    and the scratch block."""
    if window_blocks is None:
        slots = cfg.window_slots if slots is None else slots
        window_blocks = 1 + slots * paged.window_blocks_a_slot(
            cfg.sliding_window, cfg.prefill_span, block_size
        )
    shape = (cfg.n_kv_head, block_size, cfg.head_dim)
    part = lambda layers, n: {  # noqa: E731
        "k": jnp.zeros((layers, n, *shape), cfg.dtype), "v": jnp.zeros((layers, n, *shape), cfg.dtype),
    }
    return {"full": part(cfg.layers_of(FULL), num_blocks), "window": part(cfg.layers_of(SLIDING), window_blocks)}


def paged_prefill(
    params, tokens, length, start, table, pool, cfg: AfmoeConfig, *,
    block_size: int, slot=None, with_picks: bool = False,
):
    """Prefill positions [start, start + T) of one sequence; operands as
    :func:`ray_tpu.models.paged.paged_prefill` (``slot`` names nothing here),
    ``table`` [2, W] a kind, or [W] for both. ``start > 0`` continues a
    sequence whose earlier rows are in the pool under the tables: a later
    chunk. Each layer writes the chunk's keys and values, then reads its part
    a stretch of the table at a time: a full layer from position 0, a sliding
    layer from the block that holds ``start - sliding_window + 1``. Returns
    ``(pool, last_logits [vocab] float32, counts int32 [expert layers, 2])``,
    and with ``with_picks`` the chosen experts [expert layers, T, k]."""
    T = tokens.shape[1]
    tables = _by_kind(table if table.ndim == 2 else jnp.stack([table, table]))
    pos = start + jnp.arange(T, dtype=jnp.int32)
    valid = jnp.arange(T) < length
    with stage("attn_proj"):
        rope = _rope(cfg, pos)
    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[tokens[0]]
        if cfg.mup:
            x = x * jnp.asarray(cfg.d_model**0.5, cfg.dtype)
    pool = {part: dict(kv) for part, kv in pool.items()}
    seen: list = []
    for layer, p, part, l, window in _layers(params, cfg):
        with stage("attn_proj"):
            a = _rms_norm(x, p["in_norm"], cfg.rms_eps)
        q, k, v, g = _qkvg(a, p, cfg, rope if window else None)
        tab, kv = tables[part], pool[part]
        kv["k"] = paged._write_blocks(kv["k"], l, tab, start, k, block_size)
        kv["v"] = paged._write_blocks(kv["v"], l, tab, start, v, block_size)
        o = paged.prefill_attention(
            q, kv["k"], kv["v"], l, tab, pos, start + length, block_size=block_size, window=window,
        )
        x = _gated_out(x, o, g, p, cfg)
        x = _ffn(x, p, cfg, layer, valid, seen)
    with stage("embed_head"):
        last = jax.lax.dynamic_index_in_dim(x, (length - 1).astype(jnp.int32), 0, keepdims=False)
    logits = final_logits(params, last[None], cfg)[0]
    return outputs(pool, logits, seen, with_picks)


def paged_decode(
    params, last_tokens, positions, tables, pool, cfg: AfmoeConfig, *,
    block_size: int, live=None, with_picks: bool = False, interpret: bool = False,
):
    """One token a slot; operands as :func:`ray_tpu.models.paged.paged_decode`,
    ``tables`` [B, 2, W] a kind, or [B, W] for both, plus ``live`` [B] bool: a
    slot that is not live (free, or still prefilling in chunks) is routed to
    no expert; its logits mean nothing and its key and value go where its
    tables point (the scratch block, or the next chunk's first position).
    Each layer writes the step's key and value, then attends positions [0,
    position] of every slot, a sliding layer the last ``sliding_window`` of
    them: over the live blocks in place or over the gathered table
    (:func:`ray_tpu.models.paged.decode_attention`; ``interpret`` runs its
    kernel in the Pallas interpreter: the tests). Returns ``(pool, logits [B,
    vocab] float32, counts)``."""
    B = last_tokens.shape[0]
    tables = _by_kind(tables if tables.ndim == 3 else jnp.stack([tables, tables], axis=1))
    attend = {
        "full": paged.decode_attention(paged.attention_kind(cfg), block_size, None, interpret),
        "window": paged.decode_attention(
            paged.attention_kind(cfg, cfg.sliding_window), block_size, None, interpret
        ),
    }
    with stage("pool_write"):
        rows = jnp.arange(B)
        offs = positions % block_size
    with stage("attn_core"):
        lengths = positions + 1  # the step's own key is attended
    with stage("attn_proj"):
        rope = _rope(cfg, positions)
    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[last_tokens]
        if cfg.mup:
            x = x * jnp.asarray(cfg.d_model**0.5, cfg.dtype)
    pool = {part: dict(kv) for part, kv in pool.items()}
    seen: list = []
    for layer, p, part, l, window in _layers(params, cfg):
        with stage("attn_proj"):
            a = _rms_norm(x, p["in_norm"], cfg.rms_eps)
        q, k, v, g = _qkvg(a, p, cfg, rope if window else None)
        tab, kv = tables[part], pool[part]
        with stage("pool_write"):
            bids = tab[rows, positions // block_size]
        kv["k"] = paged._write(kv["k"], l, bids, offs, k)
        kv["v"] = paged._write(kv["v"], l, bids, offs, v)
        with stage("attn_core"):
            o = attend[part](q, kv["k"], kv["v"], jnp.asarray(l, jnp.int32), tab, lengths)
        x = _gated_out(x, o, g, p, cfg)
        x = _ffn(x, p, cfg, layer, live, seen)
    return outputs(pool, final_logits(params, x, cfg), seen, with_picks)
