"""Nemotron-H (``model_type: nemotron_h``; NVIDIA-Nemotron-3-Super-120B-A12B): a
hybrid decoder whose block is ONE sublayer, ``x += Mixer(RMSNorm(x))``, the
mixer chosen by the block's letter of ``hybrid_override_pattern``: ``M`` a
Mamba-2 state-space layer, ``*`` grouped-query attention without rotation,
``E`` a routed mixture of squared-ReLU experts computed in a latent, with one
shared expert at the model's width. Final RMSNorm; untied head. Fifth model
family of the serving tier, and the first that keeps keys and values per head
in blocks *and* a state per slot.

- **M.** ``[z | xBC | dt] = W_in u``; ``xBC = silu(conv(xBC) + b)``, a causal
  depthwise convolution of width 4, split into ``x`` [H, P], ``B`` and ``C`` [G,
  N]; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the recurrence of
  :mod:`ray_tpu.ops.ssd` on a float32 state [H, P, N]; the gated norm
  ``RMSNorm(y silu(z))`` over each of the G groups of channels; ``W_out``.
  :func:`mamba_prefill` runs the chunked scan, :func:`mamba_decode` one step.
- **\\*.** ``n_head`` query heads over ``n_kv_head`` key/value heads, no biases,
  no rotation, scale ``head_dim^-1/2``. Keys and values lie in the block pool
  under the engine's block tables, as :mod:`ray_tpu.models.paged` keeps them:
  prefill writes whole blocks and gathers with its ``_write_blocks_read``;
  decode writes a row with its ``_write`` and attends through its
  ``decode_attention`` (the kernel of ``ops/paged_attention.py`` over the live
  blocks on a TPU, the gather elsewhere).
- **E.** :func:`ray_tpu.models.latent_moe.moe_ffn` with what this family's
  parameters hold: no gates, ``latent_in`` / ``latent_out`` around the routed
  part, a selection bias that :func:`init_params` balances
  (:func:`latent_moe.balance_routers`, shared with ``kimi_linear``).

The cache is ``{"k", "v": [blocks *, N, KH, block, Dh], "state": [blocks M,
slots + 1, H, P, N] float32, "conv": [blocks M, slots + 1, 3, H P + 2 G N]}``:
blocks of rows per head and a state per slot (:mod:`ray_tpu.models.paged`, "What
a pool is made of"). Row ``slots`` of the last two is scratch: a prefill that
names no slot runs there. The multi-token-prediction head of the published
model is a drafter beside it and is not here.
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
from ray_tpu.ops import state_step
from ray_tpu.ops.ssd import ssd_chunked

Params = dict
_F32 = jnp.float32

PUBLISHED_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"
)



@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Published key meanings (``config.json``); defaults are the published
    Nemotron-3-Super-120B-A12B sizes, uncut."""

    family: ClassVar[str] = "nemotron_h"

    vocab_size: int = 131072  # rows of the embedding and the head held here
    d_model: int = 4096
    pattern: str = PUBLISHED_PATTERN  # hybrid_override_pattern, as published
    n_layer: int = 88  # the blocks held here: the pattern's first n_layer
    # Mamba-2
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    ssm_groups: int = 8  # n_groups: of B, C and the gated norm
    ssm_state: int = 128
    conv_kernel: int = 4
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # Attention
    n_head: int = 32
    n_kv_head: int = 2
    head_dim: int = 128
    # Experts
    moe_latent: int = 1024  # moe_latent_size: the routed experts' input and output
    moe_d_ff: int = 2688
    shared_d_ff: int = 5376  # moe_shared_expert_intermediate_size
    n_experts: int = 512  # the router's width: all routed experts of the model
    experts_held: int = 512  # of them, the ones whose weights are here ...
    expert_offset: int = 0  # ... starting from this one
    experts_per_token: int = 22
    n_group: int = 1  # the grouped top-k is a plain one
    topk_group: int = 1
    routed_scaling: float = 5.0
    renormalize: bool = True  # norm_topk_prob
    hidden_act: str = "relu2"  # mlp_hidden_act: of experts that have no gate
    # Serving
    max_seq: int = 2048
    state_slots: int = 16  # state rows where the caller names no count
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        assert set(self.pattern) <= set("ME*"), self.pattern
        assert 1 <= self.n_layer <= len(self.pattern)
        assert 0 <= self.expert_offset
        assert self.expert_offset + self.experts_held <= self.n_experts
        assert self.mamba_heads % self.ssm_groups == 0 and self.n_head % self.n_kv_head == 0

    @property
    def held(self) -> str:
        """The letters of the blocks held here."""
        return self.pattern[: self.n_layer]

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def n_moe_layers(self) -> int:
        return self.held.count("E")

    @staticmethod
    def tiny(
        pattern: str = "MEM*EME", vocab_size: int = 512, max_seq: int = 256,
        experts_held: int = 8, expert_offset: int = 0, **kw,
    ) -> "NemotronHConfig":
        """A CPU-test size with every kind of block."""
        return NemotronHConfig(**{**dict(
            vocab_size=vocab_size, d_model=64, pattern=pattern, n_layer=len(pattern),
            mamba_heads=4, mamba_head_dim=8, ssm_groups=2, ssm_state=16,
            n_head=4, n_kv_head=2, head_dim=16, moe_latent=16, moe_d_ff=32, shared_d_ff=48,
            n_experts=8, experts_held=experts_held, expert_offset=expert_offset,
            experts_per_token=3, max_seq=max_seq, state_slots=4,
            dtype=jnp.float32, param_dtype=jnp.float32,
        ), **kw})


# ---------------------------------------------------------------------------
# Parameters

# What init_params balances the routers' selection bias over (balance_routers).
_BALANCE_ROUNDS, _BALANCE_TOKENS = 96, 1024


@functools.partial(jax.jit, static_argnames="cfg")
def init_params(key: jax.Array, cfg: NemotronHConfig) -> Params:
    """Random weights (:func:`draw_params`) with each router's selection bias
    balanced as a served checkpoint's is. One program, which the compile
    cache keeps."""
    key, sub = jax.random.split(key)
    return latent_moe.balance_routers(
        draw_params(key, cfg), sub, cfg, _BALANCE_ROUNDS, min(_BALANCE_TOKENS, cfg.max_seq),
        init_pool, paged_prefill,
    )


def draw_params(key: jax.Array, cfg: NemotronHConfig) -> Params:
    """Random weights, drawn tensor by tensor in the parameter dtype: no
    float32 copy of an expert stack is ever live. N(0, 0.02); the projections
    that write to the residual stream (``w_out``, ``wo``, ``latent_out``,
    ``s_down``) scaled by 1/sqrt(blocks held), as ``rescale_prenorm_residual``
    scales them; the convolution U(-K^-1/2, K^-1/2), weights and bias; ``A_log
    = log U[1, 16]``, ``dt_bias`` the inverse softplus of a step log-uniform in
    [``time_step_min``, ``time_step_max``] and floored; ``D`` and norms one;
    the experts' down projections with zero sums over their hidden units
    (``down`` below says why); the router in float32 with unit-variance logits
    and a zero selection bias."""
    pd = cfg.param_dtype
    D, H, K = cfg.d_model, cfg.mamba_heads, cfg.conv_kernel
    E, Dl, Fm, Fs = cfg.experts_held, cfg.moe_latent, cfg.moe_d_ff, cfg.shared_d_ff
    std = 0.02
    resid = std / cfg.n_layer**0.5
    keys = iter(jax.random.split(key, 8 * cfg.n_layer + 8))

    def w(shape, s=std, dtype=pd):
        return jax.random.normal(next(keys), shape, dtype) * jnp.asarray(s, dtype)

    def down(shape, s=std):
        """An expert's down projection [..., F, out], each output's weights
        summing to zero over the F hidden units. An ungated squared ReLU is
        positive at every unit, about alike at all of them, and a random
        down projection turns that mean into ONE vector added to every token
        alike: after 11 blocks the slots' hidden states had a cosine of 0.80,
        their logits of 0.85, and on some seeds one token won every context,
        so that 64 greedy sequences fed the same token touched the experts
        one token touches (PERF.md section 6, PR 35). A gated expert's
        product with a zero-mean ``up`` has no such mean, and a trained
        checkpoint does not answer every context alike."""
        def one(key):  # [F, out]: drawn and centred in float32, an expert at a time, then cast
            m = jax.random.normal(key, shape[-2:], _F32) * s
            return (m - jnp.mean(m, axis=0, keepdims=True)).astype(pd)

        if len(shape) == 2:
            return one(next(keys))
        return jax.lax.map(one, jax.random.split(next(keys), shape[0]))

    def mamba():
        step = jnp.exp(jax.random.uniform(
            next(keys), (H,), _F32, jnp.log(cfg.time_step_min), jnp.log(cfg.time_step_max)))
        step = jnp.maximum(step, cfg.time_step_floor)
        conv = jax.random.uniform(next(keys), (K + 1, cfg.conv_dim), _F32, -(K**-0.5), K**-0.5)
        return {
            "w_in": w((D, cfg.d_inner + cfg.conv_dim + H)),
            "conv_w": conv[:K].astype(pd), "conv_b": conv[K].astype(pd),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1(step)
            "A_log": jnp.log(jax.random.uniform(next(keys), (H,), _F32, 1.0, 16.0)),
            "D": jnp.ones((H,), _F32),
            "gate_norm": jnp.ones((cfg.d_inner,), pd),
            "w_out": w((cfg.d_inner, D), resid),
        }

    def attention():
        return {
            "wq": w((D, cfg.n_head * cfg.head_dim)),
            "wk": w((D, cfg.n_kv_head * cfg.head_dim)),
            "wv": w((D, cfg.n_kv_head * cfg.head_dim)),
            "wo": w((cfg.n_head * cfg.head_dim, D), resid),
        }

    def experts():
        return {
            "router": w((D, cfg.n_experts), D**-0.5, _F32),
            "router_bias": jnp.zeros((cfg.n_experts,), _F32),
            "latent_in": w((D, Dl)), "latent_out": w((Dl, D), resid),
            "e_up": w((E, Dl, Fm)), "e_down": down((E, Fm, Dl)),
            "s_up": w((D, Fs)), "s_down": down((Fs, D), resid),
        }

    mixers = {"M": mamba, "*": attention, "E": experts}
    return {
        "wte": w((cfg.vocab_size, D)),
        "layers": [{"norm": jnp.ones((D,), pd), **mixers[kind]()} for kind in cfg.held],
        "final_norm": jnp.ones((D,), pd),
        "lm_head": w((D, cfg.vocab_size)),
    }


# ---------------------------------------------------------------------------
# Mamba-2 mixer


def _mamba_inputs(u, p, cfg: NemotronHConfig):
    """``u`` [..., D] normed -> ``(z [..., H P], xBC [..., conv_dim] before
    the convolution, dt [..., H] float32 after the softplus)``."""
    dt = cfg.dtype
    z, xBC, step = jnp.split(
        u @ p["w_in"].astype(dt), [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1
    )
    return z, xBC, jax.nn.softplus(step.astype(_F32) + p["dt_bias"].astype(_F32))


def _ssm_operands(mixed, p, cfg: NemotronHConfig):
    """The convolved, SiLU'd ``mixed`` [..., conv_dim] split into ``x`` [..., H,
    P], ``B``, ``C`` [..., G, N]; and ``A``, ``D`` [H]."""
    H, P, G, N = cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_groups, cfg.ssm_state
    x, B, C = jnp.split(mixed, [H * P, H * P + G * N], axis=-1)
    lead = mixed.shape[:-1]
    return (x.reshape(*lead, H, P), B.reshape(*lead, G, N), C.reshape(*lead, G, N),
            -jnp.exp(p["A_log"].astype(_F32)), p["D"])


def gated_norm(y, z, scale, cfg: NemotronHConfig):
    """``RMSNorm(y silu(z))`` over each of the ``ssm_groups`` groups of
    channels, times ``scale``: ``y`` float32 and ``z`` [..., H P]."""
    g = y * jax.nn.silu(z.astype(_F32))
    grouped = g.reshape(*g.shape[:-1], cfg.ssm_groups, -1)
    grouped = grouped * jax.lax.rsqrt(jnp.mean(grouped * grouped, axis=-1, keepdims=True) + cfg.rms_eps)
    return (grouped.reshape(g.shape) * scale.astype(_F32)).astype(cfg.dtype)


def mamba_prefill(u, p, cfg: NemotronHConfig, h0, tail, length):
    """``u`` [T, D] normed, of which the first ``length`` rows are tokens;
    ``h0`` [H, P, N] and ``tail`` [K-1, conv_dim] are the state and the last
    pre-convolution rows before row 0 (zeros at the start of a sequence).
    Returns ``(out [T, D], h, tail)`` as of row ``length``: padded rows do not
    touch the state."""
    T, K = u.shape[0], cfg.conv_kernel
    dt = cfg.dtype
    with stage("state_in"):
        z, xBC, step = _mamba_inputs(u, p, cfg)
        rows = jnp.concatenate([tail.astype(dt), xBC])  # [K-1+T, conv_dim]
        conv = p["conv_w"].astype(dt)
        mixed = sum(conv[j] * rows[j : j + T] for j in range(K)) + p["conv_b"].astype(dt)
        x, B, C, A, D = _ssm_operands(jax.nn.silu(mixed), p, cfg)
        live = (jnp.arange(T) < length)[:, None]
        step = step * live
    with stage("state_scan"):
        y, h = ssd_chunked(x, step, A, B, C, D, h0)
        tail = jax.lax.dynamic_slice_in_dim(rows, length, K - 1, axis=0)
    with stage("state_out"):
        out = gated_norm(y.reshape(T, -1), z, p["gate_norm"], cfg) @ p["w_out"].astype(dt)
    return out, h, tail


def mamba_decode(u, p, cfg: NemotronHConfig, h, tail):
    """One token a row: ``u`` [B, D], ``h`` [B, H, P, N] (or the rows where
    they lie, a :class:`ray_tpu.ops.state_step.Rows`), ``tail`` [B, K-1,
    conv_dim]. Returns ``(out [B, D], h, tail)``."""
    dt = cfg.dtype
    with stage("state_in"):
        z, xBC, step = _mamba_inputs(u, p, cfg)
        rows = jnp.concatenate([tail.astype(dt), xBC[:, None]], axis=1)  # [B, K, conv_dim]
        mixed = jnp.einsum("kc,bkc->bc", p["conv_w"].astype(dt), rows) + p["conv_b"].astype(dt)
        x, B, C, A, D = _ssm_operands(jax.nn.silu(mixed), p, cfg)
    with stage("state_scan"):
        y, h = state_step.ssd(x, step, A, B, C, D, h)
    with stage("state_out"):
        out = gated_norm(y.reshape(y.shape[0], -1), z, p["gate_norm"], cfg) @ p["w_out"].astype(dt)
    with stage("state_scan"):
        return out, h, rows[:, 1:]


# ---------------------------------------------------------------------------
# Attention mixer (keys and values in the block pool, models/paged.py's way)


@stage("attn_proj")
def _qkv(u, p, cfg: NemotronHConfig):
    """``(q [..., KH, group, Dh], k [..., KH, Dh], v [..., KH, Dh])``."""
    dt = cfg.dtype
    KH, Dh = cfg.n_kv_head, cfg.head_dim
    lead = u.shape[:-1]
    q = (u @ p["wq"].astype(dt)).reshape(*lead, KH, cfg.n_head // KH, Dh)
    k = (u @ p["wk"].astype(dt)).reshape(*lead, KH, Dh)
    v = (u @ p["wv"].astype(dt)).reshape(*lead, KH, Dh)
    return q, k, v


@stage("attn_core")
def causal_attention(q, kd, vd, pos, cfg, scale=None):
    """``q`` [T, KH, group, Dh] at positions ``pos`` [T] against a table's
    gathered keys and values ``kd``, ``vd`` [KH, S, Dh] under the mask ``column
    <= position``, scores in float32 times ``scale`` (None: ``head_dim^-1/2``);
    [T, n_head Dh]."""
    T, S = q.shape[0], kd.shape[1]
    s = jnp.einsum("tkgd,ksd->kgts", q, kd).astype(_F32) * (cfg.head_dim**-0.5 if scale is None else scale)
    s = jnp.where((jnp.arange(S)[None, :] <= pos[:, None])[None, None], s, -1e30)
    pa = jax.nn.softmax(s, axis=-1).astype(vd.dtype)
    return jnp.einsum("kgts,ksd->tkgd", pa, vd).reshape(T, -1)


def attention_prefill(u, p, cfg: NemotronHConfig, pk, pv, l: int, table, pos, block_size: int):
    """``u`` [T, D] normed queries at consecutive positions ``pos`` [T]: their
    keys and values written under ``table`` [W], the table's row gathered back
    and attended under the mask ``column <= position``. Returns ``(out [T, D],
    pk, pv)``."""
    KH, Dh = cfg.n_kv_head, cfg.head_dim
    S = table.shape[0] * block_size
    q, k, v = _qkv(u, p, cfg)
    pk, kd = paged._write_blocks_read(pk, l, table, pos[0], k, block_size)  # [W, KH, block, Dh]
    pv, vd = paged._write_blocks_read(pv, l, table, pos[0], v, block_size)
    with stage("attn_core"):
        kd = kd.transpose(1, 0, 2, 3).reshape(KH, S, Dh)
        vd = vd.transpose(1, 0, 2, 3).reshape(KH, S, Dh)
    o = causal_attention(q, kd, vd, pos, cfg)
    with stage("attn_proj"):
        return o @ p["wo"].astype(cfg.dtype), pk, pv


def attention_decode(u, p, cfg: NemotronHConfig, pk, pv, l: int, tables, positions, block_size, attend):
    """One query a slot: its key and value written at ``positions`` [B] under
    ``tables`` [B, W], then positions [0, position] attended by ``attend``
    (:func:`ray_tpu.models.paged.decode_attention`). Returns ``(out [B, D], pk,
    pv)``."""
    B = u.shape[0]
    q, k, v = _qkv(u, p, cfg)
    with stage("pool_write"):
        bids = tables[jnp.arange(B), positions // block_size]
        offs = positions % block_size
    pk = paged._write(pk, l, bids, offs, k)
    pv = paged._write(pv, l, bids, offs, v)
    with stage("attn_core"):
        o = attend(q, pk, pv, jnp.asarray(l, jnp.int32), tables, positions + 1)
    with stage("attn_proj"):
        return o.reshape(B, -1) @ p["wo"].astype(cfg.dtype), pk, pv


# ---------------------------------------------------------------------------
# What the engine writes on a span


def span_fields(cfg: NemotronHConfig, counts, tokens: int, slots: int, decode=None) -> dict:
    """:func:`ray_tpu.models.latent_moe.span_fields` of the expert blocks, and
    the rows of the state that the run stepped (``slots`` sequences).
    ``decode`` names nothing here: there are no latent rows, and the attention
    blocks' rows are the engine's own ``kv_blocks_live`` times the block size."""
    return {**latent_moe.span_fields(cfg, counts, tokens), "state_slots": slots}


# ---------------------------------------------------------------------------
# The paged programs (models/paged.py dispatches here by cfg.family)


def cache(cfg: NemotronHConfig) -> paged.Cache:
    """Keys and values per head in blocks (the attention blocks'), a Mamba-2
    state and a tail per slot."""
    return paged.Cache(slot_state=True)


def init_pool(cfg: NemotronHConfig, num_blocks: int, block_size: int, slots=None):
    """The zeroed cache: keys and values per head in blocks, state and
    convolution tail by slot with one scratch row more (docstring of this
    module)."""
    slots = cfg.state_slots if slots is None else slots
    n_m, n_a = cfg.held.count("M"), cfg.held.count("*")
    kv = (n_a, num_blocks, cfg.n_kv_head, block_size, cfg.head_dim)
    return {
        "k": jnp.zeros(kv, cfg.dtype),
        "v": jnp.zeros(kv, cfg.dtype),
        "state": jnp.zeros((n_m, slots + 1, cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state), _F32),
        "conv": jnp.zeros((n_m, slots + 1, cfg.conv_kernel - 1, cfg.conv_dim), cfg.dtype),
    }


# The stage a block's norm feeds, by the block's letter.
_NORM_FEEDS = {"M": "state_in", "*": "attn_proj", "E": "experts"}


def _layers(params, cfg):
    """(the block's letter, its parameters, its index among blocks of its kind)."""
    seen = dict.fromkeys("ME*", 0)
    for kind, p in zip(cfg.held, params["layers"]):
        yield kind, p, seen[kind]
        seen[kind] += 1


def _experts(x, u, p, cfg, valid, seen: list):
    """An ``E`` block with its residual; its counts and picks go to ``seen``."""
    y, counts, picks = moe_ffn(u, p, cfg, valid)
    seen.append((counts, picks))
    with stage("experts"):
        return x + y


def paged_prefill(
    params, tokens, length, start, table, pool, cfg: NemotronHConfig, *,
    block_size: int, slot=None, with_picks: bool = False,
):
    """Prefill positions [start, start + T) of one sequence; operands as
    :func:`ray_tpu.models.paged.paged_prefill`, plus ``slot``, the row of the
    state and the convolution tail that belongs to the sequence (None: the
    scratch row). ``start == 0`` begins from zero state and an empty tail,
    whatever the slot held; ``start > 0`` continues from the slot's (a later
    chunk). Returns ``(pool, last_logits [vocab] float32, counts int32 [E
    blocks, 2])``, and with ``with_picks`` the chosen experts [E blocks, T, k]
    (for the balance and the benchmark's comparison of routing)."""
    T = tokens.shape[1]
    pk, pv, state, conv = pool["k"], pool["v"], pool["state"], pool["conv"]
    fresh = start == 0

    pos = start + jnp.arange(T, dtype=jnp.int32)
    valid = jnp.arange(T) < length
    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[tokens[0]]
    seen: list = []
    for kind, p, l in _layers(params, cfg):
        with stage(_NORM_FEEDS[kind]):
            u = _rms_norm(x, p["norm"], cfg.rms_eps)
        if kind == "M":
            out, state, conv = paged.state_prefill(
                lambda h, tail: mamba_prefill(u, p, cfg, h, tail, length),
                state, conv, l, slot, fresh,
            )
            with stage("state_out"):
                x = x + out
        elif kind == "*":
            out, pk, pv = attention_prefill(u, p, cfg, pk, pv, l, table, pos, block_size)
            with stage("attn_proj"):
                x = x + out
        else:
            x = _experts(x, u, p, cfg, valid, seen)
    with stage("embed_head"):
        last = jax.lax.dynamic_index_in_dim(x, (length - 1).astype(jnp.int32), 0, keepdims=False)
    logits = final_logits(params, last[None], cfg)[0]
    return outputs({"k": pk, "v": pv, "state": state, "conv": conv}, logits, seen, with_picks)


def paged_decode(
    params, last_tokens, positions, tables, pool, cfg: NemotronHConfig, *,
    block_size: int, live=None, with_picks: bool = False, interpret: bool = False,
):
    """One token a slot; operands as :func:`ray_tpu.models.paged.paged_decode`,
    plus ``live`` [B] bool: a slot that is not live (free, or still prefilling
    in chunks) leaves its state and tail as they were and is routed to no
    expert; its logits mean nothing and its key and value go where its table
    points (the scratch block, or the next chunk's first position). None:
    every slot is live. Slot ``b``'s state is row ``b``: the rows are read and
    written where they lie, with no gather by slot. ``interpret`` runs the
    attention kernel in the Pallas interpreter (the tests). Returns ``(pool,
    logits [B, vocab] float32, counts int32 [E blocks, 2])``."""
    B = last_tokens.shape[0]
    pk, pv, state, conv = pool["k"], pool["v"], pool["state"], pool["conv"]
    attend = paged.decode_attention(paged.attention_kind(cfg), block_size, None, interpret)
    with stage("state_scan"):
        keep = None if live is None else ~live
    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[last_tokens]
    seen: list = []
    for kind, p, l in _layers(params, cfg):
        with stage(_NORM_FEEDS[kind]):
            u = _rms_norm(x, p["norm"], cfg.rms_eps)
        if kind == "M":
            out, state, conv = paged.state_decode(
                lambda h, tail: mamba_decode(u, p, cfg, h, tail), state, conv, l, B, keep
            )
            with stage("state_out"):
                x = x + out
        elif kind == "*":
            out, pk, pv = attention_decode(
                u, p, cfg, pk, pv, l, tables, positions, block_size, attend
            )
            with stage("attn_proj"):
                x = x + out
        else:
            x = _experts(x, u, p, cfg, live, seen)
    return outputs(
        {"k": pk, "v": pv, "state": state, "conv": conv}, final_logits(params, x, cfg), seen, with_picks
    )
