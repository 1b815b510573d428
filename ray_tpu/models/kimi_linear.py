"""Kimi Linear (arXiv:2510.26692, ``model_type: kimi_linear``): a hybrid
decoder of linear-attention layers (KDA: a gated delta rule with a decay per
key channel and short causal convolutions), latent-attention layers (MLA
without rotation) every fourth layer, and a routed mixture of experts with
one shared expert after a leading dense layer. Third model family of the
serving tier, and the first whose cache is not keys and values per head.

Block: ``x += Mixer(RMSNorm(x)); x += FFN(RMSNorm(x))``; final RMSNorm; untied
head. Layers are numbered from 1 as in the published ``linear_attn_config``.

What this file holds, top down:

- :class:`KimiLinearConfig` and :func:`init_params`. Layers differ in kind, so
  ``params["layers"]`` is a list with one dict a layer and the programs unroll
  it; nothing is stacked and nothing is sliced out of a stack.
- the mixers are other modules': KDA (``kda_prefill`` / ``kda_decode``) is
  :mod:`ray_tpu.models.kda`'s, shared with ``solar_open2``; the latent mixer
  (``mla_latent``, ``mla_prefill``, ``mla_decode``) and the expert layer
  (``route``, ``moe_ffn``) are :mod:`ray_tpu.models.latent_moe`'s, shared with
  ``mla_moe``: here without rotation, without a low-rank query and with one
  expert group, by leaving those arguments off.
- the paged programs :func:`paged_prefill` / :func:`paged_decode` and
  :func:`init_pool`, which :mod:`ray_tpu.models.paged` hands a
  ``KimiLinearConfig`` to. The cache is ``{"ckv": [L_mla, N, block, 576],
  "state": [L_kda, slots + 1, H, d_k, d_v] float32, "conv": [L_kda, slots + 1,
  3, 3 H d]}``: latent rows in blocks under the engine's block tables, and a
  recurrent state and a convolution tail per slot, handled as
  :func:`paged.state_prefill` and :func:`paged.state_decode` say. Row ``slots``
  of the last two is scratch: a prefill that names no slot runs there.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from ray_tpu.models import latent_moe, paged
from ray_tpu.models.kda import draw_kda, kda_decode, kda_prefill
from ray_tpu.models.latent_moe import (  # noqa: F401 -- moe_ffn and route: the family's surface
    ffn,
    final_logits,
    mla_decode,
    mla_latent,
    mla_prefill,
    moe_ffn,
    outputs,
    route,
)
from ray_tpu.models.common import _rms_norm, stage

Params = dict
_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """Published key meanings (``config.json``); defaults are the published
    Kimi-Linear-48B-A3B sizes, uncut."""

    family: ClassVar[str] = "kimi_linear"

    vocab_size: int = 163840  # rows of the embedding and the head held here
    n_layer: int = 27
    d_model: int = 2304
    kda_layers: tuple = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26)
    mla_layers: tuple = (4, 8, 12, 16, 20, 24, 27)
    # KDA (linear_attn_config)
    kda_heads: int = 32
    kda_head_dim: int = 128  # d_k = d_v
    conv_kernel: int = 4
    kda_gate_rank: int = 128  # of the decay's and the output gate's low-rank pairs
    kda_neg_eigval: bool = False  # beta in (0, 2) and not (0, 1): this model's is not
    # MLA
    n_head: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64  # the shared key part; not rotated (mla_use_nope)
    v_head_dim: int = 128
    # Feed-forward
    d_ff: int = 9216  # the dense layers'
    first_k_dense: int = 1
    moe_d_ff: int = 1024
    n_experts: int = 256  # the router's width: all routed experts of the model
    experts_held: int = 256  # of them, the ones whose weights are here ...
    expert_offset: int = 0  # ... starting from this one
    experts_per_token: int = 8
    n_shared_experts: int = 1
    n_group: int = 1  # num_expert_group: the grouped top-k is a plain one
    topk_group: int = 1
    routed_scaling: float = 2.446
    renormalize: bool = True
    hidden_act: str = "silu"  # of the experts, which have a gate (SwiGLU)
    # Serving
    max_seq: int = 2048
    state_slots: int = 16  # state rows where the caller names no count
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        layers = sorted(self.kda_layers + self.mla_layers)
        assert layers == list(range(1, self.n_layer + 1)), layers
        assert 0 <= self.expert_offset
        assert self.expert_offset + self.experts_held <= self.n_experts

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_row_dim(self) -> int:
        """A pool row's width: the latent row as it is, 4.5 lane tiles (the
        accepted check reads the rows whole at this width)."""
        return self.latent_dim

    @property
    def conv_dim(self) -> int:
        return 3 * self.kda_heads * self.kda_head_dim

    def mixer(self, layer: int) -> str:
        return "mla" if layer in self.mla_layers else "kda"

    def is_moe(self, layer: int) -> bool:
        return layer > self.first_k_dense

    @property
    def n_moe_layers(self) -> int:
        return self.n_layer - self.first_k_dense

    @staticmethod
    def tiny(
        n_layer: int = 5,
        vocab_size: int = 512,
        max_seq: int = 256,
        experts_held: int = 8,
        expert_offset: int = 0,
        **kw,
    ) -> "KimiLinearConfig":
        """A CPU-test size with every kind of layer: dense + KDA first, an
        MLA layer every fourth."""
        mla = tuple(i for i in range(1, n_layer + 1) if i % 4 == 0)
        return KimiLinearConfig(**{**dict(
            vocab_size=vocab_size, n_layer=n_layer, d_model=64,
            kda_layers=tuple(i for i in range(1, n_layer + 1) if i not in mla),
            mla_layers=mla, kda_heads=2, kda_head_dim=16, kda_gate_rank=8,
            n_head=2, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, d_ff=128, moe_d_ff=32, n_experts=8,
            experts_held=experts_held, expert_offset=expert_offset,
            experts_per_token=2, max_seq=max_seq,
            state_slots=4, dtype=jnp.float32, param_dtype=jnp.float32,
        ), **kw})


# ---------------------------------------------------------------------------
# Parameters


# What init_params balances the routers' selection bias over (balance_routers).
_BALANCE_ROUNDS, _BALANCE_TOKENS = 96, 1024


@functools.partial(jax.jit, static_argnames="cfg")
def init_params(key: jax.Array, cfg: KimiLinearConfig) -> Params:
    """Random weights (:func:`draw_params`) with each router's selection bias
    balanced as a served checkpoint's is (:func:`balance_routers`). One
    program (a replica that draws three hundred tensors one by one spends a
    minute on it, my chip run, PR 29), which the compile cache keeps."""
    key, sub = jax.random.split(key)
    return balance_routers(
        draw_params(key, cfg), sub, cfg, _BALANCE_ROUNDS, min(_BALANCE_TOKENS, cfg.max_seq)
    )


def draw_params(key: jax.Array, cfg: KimiLinearConfig) -> Params:
    """Random weights, drawn tensor by tensor in the parameter dtype: no
    float32 copy of an expert stack is ever live. N(0, 0.02), residual
    projections scaled by 1/sqrt(2 L); the router in float32 with unit-variance
    logits and a zero selection bias; the decay's ``A_log`` and ``dt_bias`` as
    the published modelling code draws them (A in [1, 16], a time step in
    [0.001, 0.1])."""
    pd = cfg.param_dtype
    D = cfg.d_model
    Fm, E = cfg.moe_d_ff, cfg.experts_held
    std = 0.02
    resid = std / (2 * cfg.n_layer) ** 0.5
    keys = iter(jax.random.split(key, 32 * cfg.n_layer + 8))

    def w(shape, s=std, dtype=pd):
        return jax.random.normal(next(keys), shape, dtype) * jnp.asarray(s, dtype)

    def mla():
        Hm = cfg.n_head
        return {
            "wq": w((D, Hm * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))),
            "wkva": w((D, cfg.latent_dim)),
            "kv_norm": jnp.ones((cfg.kv_lora_rank,), pd),
            "wkvb": w((cfg.kv_lora_rank, Hm * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
            "wo": w((Hm * cfg.v_head_dim, D), resid),
        }

    def dense():
        return {"w_gate": w((D, cfg.d_ff)), "w_up": w((D, cfg.d_ff)),
                "w_down": w((cfg.d_ff, D), resid)}

    def moe():
        Fs = Fm * cfg.n_shared_experts
        return {
            "router": w((D, cfg.n_experts), D**-0.5, _F32),
            "router_bias": jnp.zeros((cfg.n_experts,), _F32),
            "e_gate": w((E, D, Fm)), "e_up": w((E, D, Fm)),
            "e_down": w((E, Fm, D), resid),
            "s_gate": w((D, Fs)), "s_up": w((D, Fs)), "s_down": w((Fs, D), resid),
        }

    layers = []
    for i in range(1, cfg.n_layer + 1):
        layers.append({
            "attn_norm": jnp.ones((D,), pd),
            **(mla() if cfg.mixer(i) == "mla" else draw_kda(w, keys, cfg, resid)),
            "mlp_norm": jnp.ones((D,), pd),
            **(moe() if cfg.is_moe(i) else dense()),
        })
    return {
        "wte": w((cfg.vocab_size, D)),
        "layers": layers,
        "final_norm": jnp.ones((D,), pd),
        "lm_head": w((D, cfg.vocab_size)),
    }


# ---------------------------------------------------------------------------
# What the engine writes on a span (the latent mixer and the expert layer are
# latent_moe's: module docstring)


def span_fields(cfg: KimiLinearConfig, counts, tokens: int, slots: int, decode=None) -> dict:
    """:func:`ray_tpu.models.latent_moe.span_fields`, and the rows of the
    state that the run stepped (``slots`` sequences)."""
    return {**latent_moe.span_fields(cfg, counts, tokens, decode), "state_slots": slots}


# ---------------------------------------------------------------------------
# The paged programs (models/paged.py dispatches here by cfg.family)



def cache(cfg: KimiLinearConfig) -> paged.Cache:
    """Latent rows in blocks, a delta-rule state and a tail per slot."""
    return paged.Cache(slot_state=True, delta_rule=True, per_head=False)


def init_pool(cfg: KimiLinearConfig, num_blocks: int, block_size: int, slots=None):
    """The zeroed cache: latent rows in blocks, state and convolution tail by
    slot with one scratch row more (docstring of this module)."""
    slots = cfg.state_slots if slots is None else slots
    H, d = cfg.kda_heads, cfg.kda_head_dim
    Lk, Lm = len(cfg.kda_layers), len(cfg.mla_layers)
    return {
        "ckv": jnp.zeros((Lm, num_blocks, block_size, cfg.pool_row_dim), cfg.dtype),
        "state": jnp.zeros((Lk, slots + 1, H, d, d), _F32),
        "conv": jnp.zeros((Lk, slots + 1, cfg.conv_kernel - 1, cfg.conv_dim), cfg.dtype),
    }


def _layers(params, cfg):
    """(layer number, its parameters, its index among layers of its kind)."""
    seen = {"kda": 0, "mla": 0}
    for i, p in enumerate(params["layers"], start=1):
        kind = cfg.mixer(i)
        yield i, p, kind, seen[kind]
        seen[kind] += 1


def paged_prefill(
    params, tokens, length, start, table, pool, cfg: KimiLinearConfig, *,
    block_size: int, slot=None, with_picks: bool = False, interpret: bool = False,
):
    """Prefill positions [start, start + T) of one sequence; operands as
    :func:`ray_tpu.models.paged.paged_prefill`, plus ``slot``, the row of the
    state and the convolution tail that belongs to the sequence (None: the
    scratch row). ``start == 0`` begins from zero state, whatever the slot
    held; ``start > 0`` continues from the slot's (a later chunk). Returns
    ``(pool, last_logits [vocab] float32, counts int32 [expert layers, 2])``,
    and with ``with_picks`` the chosen experts [expert layers, T, k] (for the
    benchmark's comparison of routing). ``interpret``: the KDA layers' scan
    kernel in the Pallas interpreter (the tests)."""
    T = tokens.shape[1]
    ckv, state, conv = pool["ckv"], pool["state"], pool["conv"]
    fresh = start == 0

    pos = start + jnp.arange(T, dtype=jnp.int32)
    valid = jnp.arange(T) < length
    with stage("pool_write"):
        bids, offs = table[pos // block_size], pos % block_size
    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[tokens[0]]
    seen: list = []
    for i, p, kind, l in _layers(params, cfg):
        mixer_in, mixer_out = ("state_in", "state_out") if kind == "kda" else ("attn_proj", "attn_proj")
        with stage(mixer_in):
            h = _rms_norm(x, p["attn_norm"], cfg.rms_eps)
        if kind == "kda":
            out, state, conv = paged.state_prefill(
                lambda S, tail: kda_prefill(h, p, cfg, S, tail, length),
                state, conv, l, slot, fresh, scan_rows=T, interpret=interpret,
            )
        else:
            row = mla_latent(h, p, cfg)
            with stage("pool_write"):
                ckv = ckv.at[l, bids, offs].set(row)
            out = mla_prefill(
                h, ckv, l, table, pos, start + length, p, cfg, block_size=block_size
            )
        with stage(mixer_out):
            x = x + out
        x = ffn(x, p, cfg, i, valid, seen)
    with stage("embed_head"):
        last = jax.lax.dynamic_index_in_dim(x, (length - 1).astype(jnp.int32), 0, keepdims=False)
    logits = final_logits(params, last[None], cfg)[0]
    return outputs({"ckv": ckv, "state": state, "conv": conv}, logits, seen, with_picks)


def paged_decode(
    params, last_tokens, positions, tables, pool, cfg: KimiLinearConfig, *,
    block_size: int, live=None, with_picks: bool = False, interpret: bool = False,
):
    """One token a slot; operands as :func:`ray_tpu.models.paged.paged_decode`,
    plus ``live`` [B] bool: a slot that is not live (free, or still prefilling
    in chunks) leaves its state and tail as they were and is routed to no
    expert; its logits mean nothing. None: every slot is live. The latent
    layers attend as :func:`ray_tpu.models.paged.latent_decode_attention`
    chooses (``interpret``: its kernel in the Pallas interpreter, the tests).
    Returns ``(pool, logits [B, vocab] float32, counts int32 [expert layers,
    2])``."""
    B = last_tokens.shape[0]
    ckv, state, conv = pool["ckv"], pool["state"], pool["conv"]
    with stage("state_scan"):
        keep = None if live is None else ~live
    with stage("pool_write"):
        bids = tables[jnp.arange(B), positions // block_size]
        offs = positions % block_size
    with stage("attn_core"):
        lengths = positions + 1  # the step's own row is attended
    attend = paged.latent_decode_attention(
        cfg, block_size, None, interpret, latent_moe.mla_scale(cfg)
    )
    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[last_tokens]
    seen: list = []
    for i, p, kind, l in _layers(params, cfg):
        mixer_in, mixer_out = ("state_in", "state_out") if kind == "kda" else ("attn_proj", "attn_proj")
        with stage(mixer_in):
            h = _rms_norm(x, p["attn_norm"], cfg.rms_eps)
        if kind == "kda":
            out, state, conv = paged.state_decode(
                lambda S, tail: kda_decode(h, p, cfg, S, tail), state, conv, l, B, keep
            )
        else:
            row = mla_latent(h, p, cfg)
            with stage("pool_write"):
                ckv = ckv.at[l, bids, offs].set(row)
            out = mla_decode(h, ckv, l, tables, lengths, p, cfg, attend)
        with stage(mixer_out):
            x = x + out
        x = ffn(x, p, cfg, i, live, seen)
    return outputs(
        {"ckv": ckv, "state": state, "conv": conv}, final_logits(params, x, cfg), seen, with_picks
    )


# ---------------------------------------------------------------------------
# The selection bias of a served checkpoint


@functools.partial(jax.jit, static_argnames=("cfg", "rounds", "tokens"))
def balance_routers(params, key, cfg: KimiLinearConfig, rounds: int, tokens: int):
    """:func:`ray_tpu.models.latent_moe.balance_routers` over this family's
    prefill."""
    return latent_moe.balance_routers(params, key, cfg, rounds, tokens, init_pool, paged_prefill)
