"""Latent attention in every layer and a routed mixture of experts (the
DeepSeek-V3 block, ``model_type: deepseek_v3`` / ``axk1``; ``deepseek_v32``, it
behind an indexer, lives in :mod:`ray_tpu.models.deepseek_v32`): fourth family
of the serving tier, the first whose cache is latent rows *and nothing else*.

Block, layers numbered from 1: ``x += MLA(RMSNorm(x)); x += FFN(RMSNorm(x))``;
the first ``first_k_dense`` layers' FFN is a dense SwiGLU, the others' a routed
mixture with shared experts; final RMSNorm; untied head.

- **MLA.** ``c_q = RMSNorm(h W_dq)``, ``q = c_q W_uq`` -> per head ``[q_n;
  q_r]``; ``[c; k_r] = h W_dkv``; the
  pool's row of position t is ``[RMSNorm(c); R_t k_r]``, one for all heads;
  per head ``[k_n; v] = RMSNorm(c) W_ukv``; scores ``(q_n . k_n + R_t q_r .
  R_s k_r) * scale``, causal softmax in float32, ``W_o``. ``R_t`` rotates the
  pairs ``(2i, 2i + 1)`` of the rope part by ``t f_i`` with YaRN's
  frequencies; ``scale`` carries YaRN's ``mscale_all_dim`` squared
  (:attr:`MlaMoeConfig.softmax_scale`). Prefill expands keys and values a
  stretch of the table at a time; decode absorbs ``W_ukv``
  (:mod:`ray_tpu.models.latent_moe`, shared with ``kimi_linear``).
- **Experts.** ``sigmoid`` router in float32 over all experts of the model,
  the token held to ``topk_group`` of ``n_group`` groups of consecutive
  experts (a group scored by the sum of its two largest), top
  ``experts_per_token`` of what stays, weights renormalised and scaled; the
  experts held here (``experts_held`` from ``expert_offset``) compute their
  part, the shared expert is added. The router has weights only: a selection
  bias (``router_bias`` in a layer's parameters) is an optional term that
  :func:`init_params` does not draw.

The cache is ``{"ckv": [L, N, block, 640]}`` (the 576 values of the latent row
and zeros up to whole 128-lane tiles: :func:`latent_moe.whole_tiles` says why) under
the engine's block tables and ``BlockManager``: no state per slot, so a prefix
is shared by block ids and a prompt prefills in chunks, as for keys and values
per head. :mod:`ray_tpu.models.paged` hands an :class:`MlaMoeConfig` to
:func:`init_pool`, :func:`paged_prefill` and :func:`paged_decode`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from ray_tpu.models import latent_moe, paged
from ray_tpu.models.latent_moe import ffn, final_logits, mla_decode, mla_latent, mla_prefill
from ray_tpu.models.common import _rms_norm, stage

Params = dict
_F32 = jnp.float32



@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    """Published key meanings (``config.json``); defaults are A.X-K1's
    published sizes, uncut."""

    family: ClassVar[str] = "mla_moe"

    vocab_size: int = 163840  # rows of the embedding and the head held here
    n_layer: int = 61
    d_model: int = 7168
    # MLA
    n_head: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # Rotation of the shared key part (rope_scaling: type yarn; factor 1: plain)
    rope_theta: float = 10000.0
    rope_factor: float = 32.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # Feed-forward
    d_ff: int = 18432  # the dense layers'
    first_k_dense: int = 1
    moe_d_ff: int = 2048
    n_experts: int = 192  # the router's width: all routed experts of the model
    experts_held: int = 192  # of them, the ones whose weights are here ...
    expert_offset: int = 0  # ... starting from this one
    experts_per_token: int = 8
    n_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    routed_scaling: float = 2.5
    renormalize: bool = True  # norm_topk_prob
    hidden_act: str = "silu"  # of the experts, which have a gate (SwiGLU)
    # Serving
    max_seq: int = 4096
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        assert 0 <= self.expert_offset
        assert self.expert_offset + self.experts_held <= self.n_experts
        assert self.n_experts % self.n_group == 0 and self.topk_group <= self.n_group
        # a token's experts must fit the groups it is held to, and a group
        # must have the two experts its score sums
        per_group = self.n_experts // self.n_group
        assert self.experts_per_token <= self.topk_group * per_group
        assert self.n_group == 1 or per_group >= 2

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_row_dim(self) -> int:
        """A pool row's width: the latent row in whole 128-lane tiles."""
        return latent_moe.whole_tiles(self.latent_dim)

    def is_moe(self, layer: int) -> bool:
        return layer > self.first_k_dense

    @property
    def n_moe_layers(self) -> int:
        return self.n_layer - self.first_k_dense

    @property
    def rope_freqs(self):
        return latent_moe.rope_frequencies(
            self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
            self.rope_original_max, self.rope_beta_fast, self.rope_beta_slow,
        )

    @property
    def rope_mscale_ratio(self) -> float:
        """What cos and sin are multiplied by: ``ym(factor, mscale) /
        ym(factor, mscale_all_dim)``."""
        ym = latent_moe.yarn_mscale
        return ym(self.rope_factor, self.rope_mscale) / ym(self.rope_factor, self.rope_mscale_all_dim)

    @property
    def softmax_scale(self) -> float:
        """``(d_n + d_r)^-1/2 ym(factor, mscale_all_dim)^2``."""
        m = latent_moe.yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    @staticmethod
    def tiny(
        n_layer: int = 3, vocab_size: int = 512, max_seq: int = 256,
        experts_held: int = 8, expert_offset: int = 0, **kw,
    ) -> "MlaMoeConfig":
        """A CPU-test size: a dense layer, then expert layers of eight experts
        in four groups of which a token is held to two; YaRN stretched from
        an original context of 32 so that all three frequency bands exist."""
        return MlaMoeConfig(**{**dict(
            vocab_size=vocab_size, n_layer=n_layer, d_model=64, n_head=2,
            q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, rope_factor=8.0, rope_original_max=32, d_ff=128, moe_d_ff=32,
            n_experts=8, experts_held=experts_held, expert_offset=expert_offset,
            experts_per_token=2, n_group=4, topk_group=2, max_seq=max_seq,
            dtype=jnp.float32, param_dtype=jnp.float32,
        ), **kw})


# ---------------------------------------------------------------------------
# Parameters

# Seeded sequences, and tokens of each, whose hidden states init_params
# centres the routers on. Many sequences, because each has a mean hidden state
# of its own (what its attention averages): a router centred on one sequence
# carries that sequence's mean into every request's picks. Of printable text,
# one byte a token (ids 32-126, as a byte-level tokenizer carries it), because
# a checkpoint is balanced over text: its few ids give the values that
# attention averages a mean that ids drawn from the whole vocabulary lack.
_ROUTER_SEQUENCES = 32
_ROUTER_TOKENS = 512
_ROUTER_TEXT_IDS = (32, 127)


@functools.partial(jax.jit, static_argnames="cfg")
def init_params(key: jax.Array, cfg: MlaMoeConfig) -> Params:
    """Random weights (:func:`draw_params`) with each router centred as
    :func:`centre_routers` says. One program, which the compile cache keeps;
    every tensor is drawn in the parameter dtype, so the program's peak is the
    weights' own size and the temporaries of one short prefill."""
    key, sub = jax.random.split(key)
    return centre_routers(
        draw_params(key, cfg), sub, cfg, _ROUTER_SEQUENCES, min(_ROUTER_TOKENS, cfg.max_seq)
    )


def draw_params(key: jax.Array, cfg: MlaMoeConfig) -> Params:
    """Random weights, drawn tensor by tensor in the parameter dtype: no
    float32 copy of an expert stack is ever live. N(0, 0.02), residual
    projections (``wo``, ``w_down``, ``e_down``, ``s_down``) scaled by
    1/sqrt(2 L); norms one; the router in float32 with unit-variance logits
    and no selection bias."""
    pd = cfg.param_dtype
    D, H, Fm, E = cfg.d_model, cfg.n_head, cfg.moe_d_ff, cfg.experts_held
    dq = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    std = 0.02
    resid = std / (2 * cfg.n_layer) ** 0.5
    keys = iter(jax.random.split(key, 16 * cfg.n_layer + 8))

    def w(shape, s=std, dtype=pd):
        return jax.random.normal(next(keys), shape, dtype) * jnp.asarray(s, dtype)

    def mla():
        return {
            "wq_a": w((D, cfg.q_lora_rank)), "q_norm": jnp.ones((cfg.q_lora_rank,), pd),
            "wq_b": w((cfg.q_lora_rank, H * dq)),
            "wkva": w((D, cfg.latent_dim)),
            "kv_norm": jnp.ones((cfg.kv_lora_rank,), pd),
            "wkvb": w((cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
            "wo": w((H * cfg.v_head_dim, D), resid),
        }

    def dense():
        return {"w_gate": w((D, cfg.d_ff)), "w_up": w((D, cfg.d_ff)),
                "w_down": w((cfg.d_ff, D), resid)}

    def moe():
        Fs = Fm * cfg.n_shared_experts
        return {
            "router": w((D, cfg.n_experts), D**-0.5, _F32),
            "e_gate": w((E, D, Fm)), "e_up": w((E, D, Fm)),
            "e_down": w((E, Fm, D), resid),
            "s_gate": w((D, Fs)), "s_up": w((D, Fs)), "s_down": w((Fs, D), resid),
        }

    layers = [
        {
            "attn_norm": jnp.ones((D,), pd), **mla(),
            "mlp_norm": jnp.ones((D,), pd), **(moe() if cfg.is_moe(i) else dense()),
        }
        for i in range(1, cfg.n_layer + 1)
    ]
    return {
        "wte": w((cfg.vocab_size, D)),
        "layers": layers,
        "final_norm": jnp.ones((D,), pd),
        "lm_head": w((D, cfg.vocab_size)),
    }


# ---------------------------------------------------------------------------
# The paged programs (models/paged.py dispatches here by cfg.family)


def cache(cfg: MlaMoeConfig) -> paged.Cache:
    """Latent rows in blocks and nothing else."""
    return paged.Cache(per_head=False)


def init_pool(cfg: MlaMoeConfig, num_blocks: int, block_size: int, slots=None):
    """The zeroed cache: latent rows in blocks, one part for all layers.
    ``slots`` sizes nothing: no state is kept by slot."""
    return {"ckv": jnp.zeros((cfg.n_layer, num_blocks, block_size, cfg.pool_row_dim), cfg.dtype)}


def _rope(cfg: MlaMoeConfig, positions):
    return latent_moe.rope_tables(cfg.rope_freqs, positions, cfg.rope_mscale_ratio)


def _attention(x, p, l: int, ckv, table, pos, n_keys, rope, cfg, block_size):
    """Layer ``l``'s attention sublayer of a prefill, with its residual: the
    latent rows of positions ``pos`` written under ``table``, then read."""
    with stage("attn_proj"):
        h = _rms_norm(x, p["attn_norm"], cfg.rms_eps)
    with stage("pool_write"):
        bids, offs = table[pos // block_size], pos % block_size
    row = mla_latent(h, p, cfg, rope, cfg.pool_row_dim)
    with stage("pool_write"):
        ckv = ckv.at[l, bids, offs].set(row)
    out = mla_prefill(
        h, ckv, l, table, pos, n_keys, p, cfg, block_size=block_size,
        rope=rope, scale=cfg.softmax_scale,
    )
    with stage("attn_proj"):
        return x + out, ckv


def _prefill_layers(params, tokens, length, start, table, ckv, cfg, block_size):
    """The layers of one prefill: ``(x [T, D], ckv, seen)``."""
    T = tokens.shape[1]
    pos = start + jnp.arange(T, dtype=jnp.int32)
    valid = jnp.arange(T) < length
    with stage("attn_proj"):
        rope = _rope(cfg, pos)
    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[tokens[0]]
    seen: list = []
    for l, p in enumerate(params["layers"]):
        x, ckv = _attention(x, p, l, ckv, table, pos, start + length, rope, cfg, block_size)
        x = ffn(x, p, cfg, l + 1, valid, seen)
    return x, ckv, seen


def paged_prefill(
    params, tokens, length, start, table, pool, cfg: MlaMoeConfig, *,
    block_size: int, slot=None, with_picks: bool = False,
):
    """Prefill positions [start, start + T) of one sequence; operands as
    :func:`ray_tpu.models.paged.paged_prefill` (``slot`` names nothing here).
    ``start > 0`` continues a sequence whose earlier rows are in the pool
    under ``table``: a later chunk, or a prefix shared by block ids. Returns
    ``(pool, last_logits [vocab] float32, counts int32 [expert layers, 2])``,
    and with ``with_picks`` the chosen experts [expert layers, T, k]."""
    x, ckv, seen = _prefill_layers(params, tokens, length, start, table, pool["ckv"], cfg, block_size)
    with stage("embed_head"):
        last = jax.lax.dynamic_index_in_dim(x, (length - 1).astype(jnp.int32), 0, keepdims=False)
    logits = final_logits(params, last[None], cfg)[0]
    return latent_moe.outputs({"ckv": ckv}, logits, seen, with_picks)


def paged_decode(
    params, last_tokens, positions, tables, pool, cfg: MlaMoeConfig, *,
    block_size: int, live=None, with_picks: bool = False, interpret: bool = False,
):
    """One token a slot; operands as :func:`ray_tpu.models.paged.paged_decode`,
    plus ``live`` [B] bool: a slot that is not live (free, or still prefilling
    in chunks) is routed to no expert; its logits mean nothing and its row goes
    where its table points (the scratch block, or the next chunk's first
    position). None: every slot is live. Each layer writes the step's row,
    then attends rows [0, position] of every slot: over the live blocks in
    place or over the gathered table
    (:func:`ray_tpu.models.paged.latent_decode_attention`; ``interpret`` runs
    its kernel in the Pallas interpreter: the tests), and ``latent_rows_read``
    on the step's span says which. Returns ``(pool, logits [B, vocab]
    float32, counts)``."""
    B = last_tokens.shape[0]
    ckv = pool["ckv"]
    with stage("pool_write"):
        bids = tables[jnp.arange(B), positions // block_size]
        offs = positions % block_size
    with stage("attn_core"):
        lengths = positions + 1  # the step's own row is attended
    attend = paged.latent_decode_attention(cfg, block_size, None, interpret, cfg.softmax_scale)
    with stage("attn_proj"):
        rope = _rope(cfg, positions)
    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[last_tokens]
    seen: list = []
    for l, p in enumerate(params["layers"]):
        with stage("attn_proj"):
            h = _rms_norm(x, p["attn_norm"], cfg.rms_eps)
        row = mla_latent(h, p, cfg, rope, cfg.pool_row_dim)
        with stage("pool_write"):
            ckv = ckv.at[l, bids, offs].set(row)
        out = mla_decode(h, ckv, l, tables, lengths, p, cfg, attend, rope)
        with stage("attn_proj"):
            x = x + out
        x = ffn(x, p, cfg, l + 1, live, seen)
    return latent_moe.outputs({"ckv": ckv}, final_logits(params, x, cfg), seen, with_picks)


def span_fields(cfg: MlaMoeConfig, counts, tokens: int, slots: int, decode=None) -> dict:
    """:func:`ray_tpu.models.latent_moe.span_fields` (``slots`` names nothing
    here: no state is stepped)."""
    return latent_moe.span_fields(cfg, counts, tokens, decode)


# ---------------------------------------------------------------------------
# Routers of a checkpoint balanced by an auxiliary loss


def centre_routers(params, key, cfg: MlaMoeConfig, sequences: int, tokens: int):
    """``params`` with each expert layer's router made orthogonal to the mean
    normed hidden state that reaches it: ``W_r -= m (m^T W_r) / (m^T m)``, ``m``
    the mean of ``RMSNorm(x)`` over ``sequences`` seeded sequences of ``tokens``
    random printable bytes (ids 32-126) run through the layers before it,
    their routers already so treated.

    A checkpoint trained with a balancing loss spreads its tokens evenly over
    its experts; random weights do not (SiLU's positive mean gives every
    hidden state a common part, every token then favours the experts whose
    columns lie along it, and which chip's share they fall into changes with
    the seed: PERF.md section 6, PR 29). This model has no selection bias to
    balance with, so the balance is put where training would put it, in the
    router's weights: with the common part taken out, an expert's logit has
    zero mean over tokens and the picks spread as chance has it. No term is
    added to the model's equations.

    The mean is over many sequences because a sequence has a mean of its own
    (the values its attention averages, a fiftieth of the energy at the
    published widths): taken over one, ``m`` is off by that much for every
    request served, and a chip's share of the picks moves with the seed twice
    as far. The sequences are text because the served ones are: over a few
    ids the values that attention averages have a mean (6% of a decode
    step's normed hidden state at the published widths, nearly all of it
    along one direction), over ids drawn from the whole vocabulary they have
    none, and a router centred on those is left tilted on every request
    (PERF.md section 6, PR 33). The sequences go through a layer one after
    another, so the temporaries are one short prefill's."""
    bs = 16
    blocks = -(-tokens // bs)
    tables = 1 + jnp.arange(sequences * blocks, dtype=jnp.int32).reshape(sequences, blocks)
    ckv = init_pool(cfg, sequences * blocks + 1, bs)["ckv"]
    pos = jnp.arange(tokens, dtype=jnp.int32)
    rope = _rope(cfg, pos)
    n_keys = jnp.asarray(tokens, jnp.int32)
    xs = params["wte"].astype(cfg.dtype)[jax.random.randint(key, (sequences, tokens), *_ROUTER_TEXT_IDS)]
    layers = []
    for l, p in enumerate(params["layers"]):

        def attend(ckv, seq, p=p, l=l):
            x, ckv = _attention(seq[0], p, l, ckv, seq[1], pos, n_keys, rope, cfg, bs)
            return ckv, x

        ckv, xs = jax.lax.scan(attend, ckv, (xs, tables))
        if cfg.is_moe(l + 1):
            m = jnp.mean(_rms_norm(xs, p["mlp_norm"], cfg.rms_eps).astype(_F32), axis=(0, 1))
            router = p["router"].astype(_F32)
            along = jnp.dot(m, router, precision=jax.lax.Precision.HIGHEST) / jnp.sum(m * m)
            p = {**p, "router": router - m[:, None] * along[None, :]}
        layers.append(p)
        if l + 1 < len(params["layers"]):
            xs = jax.lax.map(lambda x, p=p, l=l: ffn(x, p, cfg, l + 1, None, []), xs)
    return {**params, "layers": layers}
