"""Solar Open 2 (``model_type: solar_open2``; upstage Solar-Open2-250B): a hybrid
decoder whose period is one grouped-query attention layer without rotation and
behind an output gate, then three linear-attention layers (KDA: the gated delta
rule of :mod:`ray_tpu.ops.delta_rule`, here with ``beta`` in (0, 2)), every
layer followed by a routed mixture of experts with one shared expert. Seventh
model family of the serving tier, and the first whose cache is keys and values
per head in blocks, a delta-rule state per slot and a convolution tail per
slot.

Block: ``x += Mixer(RMSNorm(x)); x += MoE(RMSNorm(x))``; final RMSNorm; untied
head. Layers are numbered from 0 as in the published ``gqa_layers``; the
expert layer counts them from 1, as :mod:`latent_moe` does.

- **KDA layer.** :func:`ray_tpu.models.kda.kda_prefill` / ``kda_decode``, the
  one implementation, which reads the range of ``beta``
  off the configuration (``kda_neg_eigval``: the transition ``I - beta k k^T``
  then has the eigenvalue ``1 - beta`` in (-1, 1) along ``k``). 64 heads of 128
  here: a state of ``[64, 128, 128]`` float32 a layer and sequence.
- **GQA layer.** ``q = W_q a`` [H, Dh], ``k = W_k a``, ``v = W_v a`` [KH, Dh],
  ``g = W_g a`` [H Dh]; no rotation and no other position signal, no norm over
  a head; scores ``q.k Dh^-1/2``, causal, float32 softmax, ``H / KH`` query
  heads a key/value head; ``W_o (o sigmoid(g))``. Keys and values lie in the
  block pool under the engine's block tables: written with ``paged._write``
  (a prefill's whole blocks with ``paged._write_blocks``), read by prefill a
  stretch of the table at a time (:func:`paged.prefill_attention`) and by
  decode through :func:`paged.decode_attention` (the kernel over the live
  blocks on a TPU, the gather elsewhere).
- **Experts, in every layer.** :func:`ray_tpu.models.latent_moe.moe_ffn`: a
  float32 sigmoid router over all experts of the model, the
  ``experts_per_token`` largest of ``s + b``, weights ``s / sum(s)`` times
  ``routed_scaling``, the experts held here on the picks that land on them,
  plus the shared expert.

The cache is ``{"k", "v": [GQA layers, N, KH, block, Dh], "state": [KDA layers,
slots + 1, H, d, d] float32, "conv": [KDA layers, slots + 1, 3, 3 H d]}``. Row
``slots`` of the last two is scratch: a prefill that names no slot runs there.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from ray_tpu.models import latent_moe, paged
from ray_tpu.models.kda import draw_kda, kda_decode, kda_prefill
from ray_tpu.models.latent_moe import ffn, final_logits, outputs
from ray_tpu.models.common import _rms_norm, stage

Params = dict
_F32 = jnp.float32

GQA, KDA = "gqa", "kda"
PUBLISHED_LAYER_KINDS = (GQA, KDA, KDA, KDA) * 12  # gqa_layers 0, 4, ..., 44 of 48



@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    """Published key meanings (``config.json``); defaults are the published
    Solar-Open2-250B sizes, uncut."""

    family: ClassVar[str] = "solar_open2"

    vocab_size: int = 196608  # rows of the embedding and the head held here
    d_model: int = 4096
    layer_kinds: tuple = PUBLISHED_LAYER_KINDS  # of the layers held here, in order
    # KDA (linear_attn_config)
    kda_heads: int = 64
    kda_head_dim: int = 128  # d_k = d_v
    conv_kernel: int = 4
    kda_gate_rank: int = 128  # of the decay's and the output gate's low-rank pairs
    kda_neg_eigval: bool = True  # kda_allow_neg_eigval: beta in (0, 2)
    # GQA
    n_head: int = 64
    n_kv_head: int = 8
    head_dim: int = 128
    # Experts, in every layer
    moe_d_ff: int = 1280
    n_experts: int = 320  # the router's width: all routed experts of the model
    experts_held: int = 320  # of them, the ones whose weights are here ...
    expert_offset: int = 0  # ... starting from this one
    experts_per_token: int = 8
    n_shared_experts: int = 1
    n_group: int = 1  # the grouped top-k is a plain one
    topk_group: int = 1
    routed_scaling: float = 1.0
    renormalize: bool = True  # norm_topk_prob
    hidden_act: str = "silu"  # of the experts, which have a gate (SwiGLU)
    # Serving
    max_seq: int = 4096
    state_slots: int = 16  # state rows where the caller names no count
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # Ids whose columns of the head draw_params leaves at zero (afmoe.py says why).
    silent_ids: tuple = ()

    def __post_init__(self):
        assert self.layer_kinds and set(self.layer_kinds) <= {GQA, KDA}, self.layer_kinds
        assert 0 <= self.expert_offset
        assert self.expert_offset + self.experts_held <= self.n_experts
        assert self.n_head % self.n_kv_head == 0

    @property
    def n_layer(self) -> int:
        return len(self.layer_kinds)

    @property
    def conv_dim(self) -> int:
        return 3 * self.kda_heads * self.kda_head_dim

    def layers_of(self, kind: str) -> int:
        return self.layer_kinds.count(kind)

    def is_moe(self, layer: int) -> bool:
        return True  # first_k_dense_replace 0

    @property
    def n_moe_layers(self) -> int:
        return self.n_layer

    @staticmethod
    def tiny(
        layer_kinds=(GQA, KDA, KDA, KDA, GQA), vocab_size: int = 512, max_seq: int = 256,
        experts_held: int = 8, expert_offset: int = 0, **kw,
    ) -> "SolarOpen2Config":
        """A CPU-test size: a whole period and the next one's GQA layer."""
        return SolarOpen2Config(**{**dict(
            vocab_size=vocab_size, d_model=64, layer_kinds=tuple(layer_kinds),
            kda_heads=2, kda_head_dim=16, kda_gate_rank=8,
            n_head=4, n_kv_head=2, head_dim=16, moe_d_ff=32, n_experts=8,
            experts_held=experts_held, expert_offset=expert_offset,
            experts_per_token=2, max_seq=max_seq, state_slots=4,
            dtype=jnp.float32, param_dtype=jnp.float32,
        ), **kw})


# ---------------------------------------------------------------------------
# Parameters

# What init_params balances the routers' selection bias over (balance_routers):
# rounds, the tokens of a round's sequence, and the ids its tokens are drawn from.
_BALANCE_ROUNDS, _BALANCE_TOKENS = 96, 4096
_BALANCE_TEXT_IDS = (32, 127)


@functools.partial(jax.jit, static_argnames="cfg")
def init_params(key: jax.Array, cfg: SolarOpen2Config) -> Params:
    """Random weights (:func:`draw_params`) with each router's selection bias
    balanced as a served checkpoint's is (:func:`latent_moe.balance_routers`),
    over what is served: sequences of printable bytes (ids 32-126), as
    ``afmoe.init_params`` balances its own and for its reason. One program,
    which the compile cache keeps."""
    key, sub = jax.random.split(key)
    lo, hi = _BALANCE_TEXT_IDS
    hi = min(hi, cfg.vocab_size)

    def prefill_of_text(params, tokens, *args, **kw):
        return paged_prefill(params, lo + tokens % (hi - lo), *args, **kw)

    return latent_moe.balance_routers(
        draw_params(key, cfg), sub, cfg, _BALANCE_ROUNDS, min(_BALANCE_TOKENS, cfg.max_seq),
        init_pool, prefill_of_text,
    )


def draw_params(key: jax.Array, cfg: SolarOpen2Config) -> Params:
    """Random weights, drawn tensor by tensor in the parameter dtype: no
    float32 copy of an expert stack is ever live. N(0, 0.02), the projections
    back to the residual stream scaled by 1/sqrt(2 L) of the layers held; the
    KDA layers' by :func:`kda.draw_kda`; the router in float32 with
    unit-variance logits and a zero selection bias; norms one. ``silent_ids``:
    those columns of the head are zero."""
    pd = cfg.param_dtype
    D, H, KH, Dh = cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    E, Fm = cfg.experts_held, cfg.moe_d_ff
    std = 0.02
    resid = std / (2 * cfg.n_layer) ** 0.5
    keys = iter(jax.random.split(key, 32 * cfg.n_layer + 8))

    def w(shape, s=std, dtype=pd):
        return jax.random.normal(next(keys), shape, dtype) * jnp.asarray(s, dtype)

    def gqa():
        return {
            "wq": w((D, H * Dh)), "wk": w((D, KH * Dh)), "wv": w((D, KH * Dh)),
            "wg": w((D, H * Dh)), "wo": w((H * Dh, D), resid),
        }

    def moe():
        Fs = Fm * cfg.n_shared_experts
        return {
            "router": w((D, cfg.n_experts), D**-0.5, _F32),
            "router_bias": jnp.zeros((cfg.n_experts,), _F32),
            "e_gate": w((E, D, Fm)), "e_up": w((E, D, Fm)), "e_down": w((E, Fm, D), resid),
            "s_gate": w((D, Fs)), "s_up": w((D, Fs)), "s_down": w((Fs, D), resid),
        }

    layers = [
        {
            "attn_norm": jnp.ones((D,), pd),
            **(gqa() if kind == GQA else draw_kda(w, keys, cfg, resid)),
            "mlp_norm": jnp.ones((D,), pd),
            **moe(),
        }
        for kind in cfg.layer_kinds
    ]
    wte, head = w((cfg.vocab_size, D)), w((D, cfg.vocab_size))
    if cfg.silent_ids:
        head = head.at[:, jnp.asarray(cfg.silent_ids)].set(0)
    return {"wte": wte, "layers": layers, "final_norm": jnp.ones((D,), pd), "lm_head": head}


# ---------------------------------------------------------------------------
# GQA mixer (keys and values in the block pool, models/paged.py's way)


@stage("attn_proj")
def _qkvg(a, p, cfg: SolarOpen2Config):
    """``a`` [..., D] normed -> ``(q [..., KH, group, Dh], k, v [..., KH, Dh], g
    [..., H Dh])``: nothing is rotated and nothing normed a head."""
    dt = cfg.dtype
    H, KH, Dh = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    lead = a.shape[:-1]
    q = (a @ p["wq"].astype(dt)).reshape(*lead, KH, H // KH, Dh)
    k = (a @ p["wk"].astype(dt)).reshape(*lead, KH, Dh)
    v = (a @ p["wv"].astype(dt)).reshape(*lead, KH, Dh)
    return q, k, v, a @ p["wg"].astype(dt)


@stage("attn_proj")
def _gated_out(o, g, p, cfg: SolarOpen2Config):
    """``W_o (o sigmoid(g))``: ``o`` [..., KH, group, Dh], the gate elementwise
    over the heads' values."""
    gated = (o.reshape(g.shape).astype(_F32) * jax.nn.sigmoid(g.astype(_F32))).astype(cfg.dtype)
    return gated @ p["wo"].astype(cfg.dtype)


# ---------------------------------------------------------------------------
# What the engine writes on a span


def span_fields(cfg: SolarOpen2Config, counts, tokens: int, slots: int, decode=None) -> dict:
    """:func:`ray_tpu.models.latent_moe.span_fields` of the expert layers, and
    the rows of the state that the run stepped (``slots`` sequences). For a
    decode step, ``decode`` is ``(the live slots' positions, the rows the
    program reads a GQA layer)``: ``kv_rows_live`` is what the step's attention
    needs there (each live slot's ``position + 1`` rows), ``kv_rows_read`` what
    the arm the program was built with reads: each live slot's live blocks
    under the kernel, every slot's whole table under the gather."""
    out = {**latent_moe.span_fields(cfg, counts, tokens), "state_slots": slots}
    if decode is not None:
        positions, rows_read = decode
        out["kv_rows_read"] = int(rows_read)
        out["kv_rows_live"] = int(positions.sum()) + len(positions)
    return out


# ---------------------------------------------------------------------------
# The paged programs (models/paged.py dispatches here by cfg.family)


def cache(cfg: SolarOpen2Config) -> paged.Cache:
    """Keys and values per head in blocks (the GQA layers'), a delta-rule
    state and a tail per slot (the KDA layers')."""
    return paged.Cache(slot_state=True, delta_rule=True, prefill_in_place=True)


def init_pool(cfg: SolarOpen2Config, num_blocks: int, block_size: int, slots=None):
    """The zeroed cache: keys and values per head in blocks, state and
    convolution tail by slot with one scratch row more (docstring of this
    module)."""
    slots = cfg.state_slots if slots is None else slots
    H, d = cfg.kda_heads, cfg.kda_head_dim
    n_g, n_k = cfg.layers_of(GQA), cfg.layers_of(KDA)
    kv = (n_g, num_blocks, cfg.n_kv_head, block_size, cfg.head_dim)
    return {
        "k": jnp.zeros(kv, cfg.dtype),
        "v": jnp.zeros(kv, cfg.dtype),
        "state": jnp.zeros((n_k, slots + 1, H, d, d), _F32),
        "conv": jnp.zeros((n_k, slots + 1, cfg.conv_kernel - 1, cfg.conv_dim), cfg.dtype),
    }


def _layers(params, cfg: SolarOpen2Config):
    """(layer number from 1, its kind, its parameters, its index among layers
    of its kind)."""
    seen = {GQA: 0, KDA: 0}
    for i, (kind, p) in enumerate(zip(cfg.layer_kinds, params["layers"]), start=1):
        yield i, kind, p, seen[kind]
        seen[kind] += 1


def paged_prefill(
    params, tokens, length, start, table, pool, cfg: SolarOpen2Config, *,
    block_size: int, slot=None, with_picks: bool = False, interpret: bool = False,
):
    """Prefill positions [start, start + T) of one sequence; operands as
    :func:`ray_tpu.models.paged.paged_prefill`, plus ``slot``, the row of the
    state and the convolution tail that belongs to the sequence (None: the
    scratch row). ``start == 0`` begins from zero state and an empty tail,
    whatever the slot held; ``start > 0`` continues from the slot's, and
    attends the rows the earlier chunks left in the pool under ``table``: a
    later chunk. Returns ``(pool, last_logits [vocab] float32, counts int32
    [layers, 2])``, and with ``with_picks`` the chosen experts [layers, T, k]
    (for the balance and the benchmark's comparison of routing).
    ``interpret``: the KDA layers' scan kernel in the Pallas interpreter (the
    tests)."""
    T = tokens.shape[1]
    pk, pv, state, conv = pool["k"], pool["v"], pool["state"], pool["conv"]
    fresh = start == 0

    pos = start + jnp.arange(T, dtype=jnp.int32)
    valid = jnp.arange(T) < length
    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[tokens[0]]
    seen: list = []
    for i, kind, p, l in _layers(params, cfg):
        mixer_in, mixer_out = ("state_in", "state_out") if kind == KDA else ("attn_proj", "attn_proj")
        with stage(mixer_in):
            a = _rms_norm(x, p["attn_norm"], cfg.rms_eps)
        if kind == KDA:
            out, state, conv = paged.state_prefill(
                lambda S, tail: kda_prefill(a, p, cfg, S, tail, length),
                state, conv, l, slot, fresh, scan_rows=T, interpret=interpret,
            )
        else:
            q, k, v, g = _qkvg(a, p, cfg)
            pk = paged._write_blocks(pk, l, table, start, k, block_size)
            pv = paged._write_blocks(pv, l, table, start, v, block_size)
            o = paged.prefill_attention(
                q, pk, pv, l, table, pos, start + length, block_size=block_size
            )
            out = _gated_out(o, g, p, cfg)
        with stage(mixer_out):
            x = x + out
        x = ffn(x, p, cfg, i, valid, seen)
    with stage("embed_head"):
        last = jax.lax.dynamic_index_in_dim(x, (length - 1).astype(jnp.int32), 0, keepdims=False)
    logits = final_logits(params, last[None], cfg)[0]
    return outputs({"k": pk, "v": pv, "state": state, "conv": conv}, logits, seen, with_picks)


def paged_decode(
    params, last_tokens, positions, tables, pool, cfg: SolarOpen2Config, *,
    block_size: int, live=None, with_picks: bool = False, interpret: bool = False,
):
    """One token a slot; operands as :func:`ray_tpu.models.paged.paged_decode`,
    plus ``live`` [B] bool: a slot that is not live (free, or still prefilling
    in chunks) leaves its state and tail as they were and is routed to no
    expert; its logits mean nothing and its key and value go where its table
    points (the scratch block, or the next chunk's first position). None:
    every slot is live. Slot ``b``'s state is row ``b``, read and written where
    it lies. The GQA layers attend as :func:`paged.decode_attention` chooses
    (``interpret``: its kernel in the Pallas interpreter, the tests). Returns
    ``(pool, logits [B, vocab] float32, counts int32 [layers, 2])``."""
    B = last_tokens.shape[0]
    pk, pv, state, conv = pool["k"], pool["v"], pool["state"], pool["conv"]
    attend = paged.decode_attention(paged.attention_kind(cfg), block_size, None, interpret)
    with stage("state_scan"):
        keep = None if live is None else ~live
    with stage("pool_write"):
        bids = tables[jnp.arange(B), positions // block_size]
        offs = positions % block_size
    with stage("attn_core"):
        lengths = positions + 1  # the step's own key is attended
    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[last_tokens]
    seen: list = []
    for i, kind, p, l in _layers(params, cfg):
        mixer_in, mixer_out = ("state_in", "state_out") if kind == KDA else ("attn_proj", "attn_proj")
        with stage(mixer_in):
            a = _rms_norm(x, p["attn_norm"], cfg.rms_eps)
        if kind == KDA:
            out, state, conv = paged.state_decode(
                lambda S, tail: kda_decode(a, p, cfg, S, tail), state, conv, l, B, keep
            )
        else:
            q, k, v, g = _qkvg(a, p, cfg)
            pk = paged._write(pk, l, bids, offs, k)
            pv = paged._write(pv, l, bids, offs, v)
            with stage("attn_core"):
                o = attend(q, pk, pv, jnp.asarray(l, jnp.int32), tables, lengths)
            out = _gated_out(o, g, p, cfg)
        with stage(mixer_out):
            x = x + out
        x = ffn(x, p, cfg, i, live, seen)
    return outputs(
        {"k": pk, "v": pv, "state": state, "conv": conv}, final_logits(params, x, cfg), seen, with_picks
    )
