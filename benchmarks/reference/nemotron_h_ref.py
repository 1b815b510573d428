"""Plain reference of the Nemotron-H decoder (NVIDIA-Nemotron-3-Super-120B-A12B,
``model_type: nemotron_h``).

Published description (the model's ``config.json`` and modelling code; Mamba-2,
arXiv:2405.21060): token embedding; each block is ``x += Mixer(RMSNorm(x))``
with ONE mixer, chosen by the block's letter of ``hybrid_override_pattern``
(``M`` Mamba-2, ``*`` attention, ``E`` experts); final RMSNorm; untied head.

- **M, Mamba-2** (H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``, G =
  ``n_groups`` groups, state N = ``ssm_state_size``): ``[z | xBC | dt] = W_in
  u``, widths ``H P | H P + 2 G N | H``; ``xBC = silu(conv(xBC) + b)``, a causal
  depthwise convolution of width ``conv_kernel`` that sees zeros before the
  start; split into ``x`` [H, P], ``B`` [G, N], ``C`` [G, N], head ``h`` using
  group ``h // (H / G)``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``
  a head; state ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t`` [H, P, N], zero
  at the start; ``y_t = h_t C_t + D x_t``; the gated norm ``RMSNorm(y silu(z))``
  over each of the G groups of ``H P / G`` channels, times its weight; ``W_out``.
  Computed here token by token (``lax.scan`` over the sequence), not by chunks.
- **\\*, attention**: ``num_attention_heads`` query heads and
  ``num_key_value_heads`` key/value heads of ``head_dim``, no biases, causal
  softmax of ``q.k head_dim^-1/2``, **no rotation** and no other position
  signal (the family's attention layers apply none), ``W_o``. No cache.
- **E, experts in a latent**: ``s = sigmoid(W_r u)`` over all routed experts of
  the model, in float32; the ``num_experts_per_tok`` are chosen by ``s + bias``
  (``n_group`` = ``topk_group`` = 1: a plain top-k); weights
  ``routed_scaling_factor s_i / sum of the chosen s``; the routed part is
  computed on ``l = W_1 u``: expert ``e`` gives ``W_down,e relu(W_up,e l)^2``
  (no gate), the weighted sum over the chosen experts *that are held here*
  (``n_routed_experts`` of them from ``expert_offset``;
  ``published.n_routed_experts`` is the router's width) goes back through
  ``W_2``; plus one shared expert on ``u`` itself, ``W_sd relu(W_su u)^2``. A
  masked loop over the experts held; what the absent ones would add is left
  out, as in the program.

Departures, each also under ``assumed`` in the configuration's file: no rotation
in attention (``rope_theta`` and ``partial_rotary_factor`` are read by nothing);
the multi-token-prediction head is left out; the state is float32; weights are
random, the router's drawn so that its logits have unit variance and kept in
float32, ``e_score_correction_bias`` balanced. This file draws no weights: the
output check hands ``forward`` the ones the served program drew from the seed
(one dict a block, as ``ray_tpu.models.nemotron_h`` names them).

``wrong`` names one departure from the mathematics above, for the output
check's controls: ``ungrouped_norm`` (the gated norm over all ``H P`` channels
at once) or ``unsquared`` (the experts' activation a plain ReLU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.common import F32, quantizer

WRONGS = ("ungrouped_norm", "unsquared")


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def ssm_recurrence(x, dt, A, Bm, Cm, keep_at):
    """Token by token from a zero state. ``x`` [B, S, H, P], ``dt`` [B, S, H],
    ``A`` [H], ``Bm``, ``Cm`` [B, S, H, N] (each head's group's) -> ``(y [B, S,
    H, P] without the D term, the state [B, H, P, N] after ``keep_at[b]``
    tokens of sequence b)``."""
    B, S, H, P = x.shape

    def step(carry, t):
        h, kept = carry
        i, x_t, dt_t, B_t, C_t = t
        h = jnp.exp(dt_t * A)[..., None, None] * h + (dt_t[..., None] * x_t)[..., None] * B_t[..., None, :]
        kept = jnp.where((i + 1 == keep_at)[:, None, None, None], h, kept)
        return (h, kept), jnp.einsum("bhpn,bhn->bhp", h, C_t)

    zero = jnp.zeros((B, H, P, Bm.shape[-1]), F32)
    (_, kept), y = jax.lax.scan(
        step, (zero, zero), (jnp.arange(S), *(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm)))
    )
    return jnp.moveaxis(y, 0, 1), kept


def route(u, p, c: dict, mm):
    """Chosen experts [..., k] and their weights, over all routed experts."""
    s = jax.nn.sigmoid(mm(u, p["router"]))
    _, idx = jax.lax.top_k(s + p["router_bias"], c["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if c["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * c["routed_scaling_factor"]


def experts(u, p, c: dict, mm, wrong=None):
    """The experts held here on their picks, in the latent and back through
    ``W_2``, plus the shared expert at full width; also the picks."""
    idx, w = route(u, p, c, mm)
    act = jax.nn.relu if wrong == "unsquared" else (lambda a: jnp.square(jax.nn.relu(a)))
    lat = mm(u, p["latent_in"])

    def one(y, e):
        n, up, down = e
        w_e = jnp.sum(jnp.where(idx == n + c["expert_offset"], w, 0.0), axis=-1)
        return y + w_e[..., None] * mm(act(mm(lat, up)), down), None

    held = jnp.arange(p["e_up"].shape[0])
    y, _ = jax.lax.scan(one, jnp.zeros_like(lat), (held, p["e_up"], p["e_down"]))
    return mm(y, p["latent_out"]) + mm(act(mm(u, p["s_up"])), p["s_down"]), idx


def mamba(u, p, c: dict, mm, keep_at, wrong=None):
    """``u`` [B, S, D] normed -> ``(out [B, S, D], the state [B, H, P, N] after
    ``keep_at[b]`` tokens of sequence b, the K - 1 pre-convolution rows before
    that position [B, K - 1, H P + 2 G N])``."""
    H, P, G, N, K = (c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"],
                     c["ssm_state_size"], c["conv_kernel"])
    B, S, _ = u.shape
    z, xBC, dt = jnp.split(mm(u, p["w_in"]), [H * P, 2 * H * P + 2 * G * N], axis=-1)
    padded = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))  # zeros before the start
    tail = jnp.stack([  # rows keep_at - K + 1 .. keep_at - 1 of xBC
        jax.lax.dynamic_slice_in_dim(padded[b], keep_at[b], K - 1, axis=0) for b in range(B)
    ])
    conv = p["conv_w"].astype(F32)
    xBC = jax.nn.silu(sum(conv[j] * padded[:, j : j + S] for j in range(K)) + p["conv_b"].astype(F32))
    x, Bm, Cm = jnp.split(xBC, [H * P, H * P + G * N], axis=-1)
    x = x.reshape(B, S, H, P)
    per_head = lambda a: jnp.repeat(a.reshape(B, S, G, N), H // G, axis=2)  # noqa: E731
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    y, kept = ssm_recurrence(x, dt, -jnp.exp(p["A_log"].astype(F32)), per_head(Bm), per_head(Cm), keep_at)
    y = (y + p["D"].astype(F32)[:, None] * x).reshape(B, S, H * P) * jax.nn.silu(z)
    y = y.reshape(B, S, 1 if wrong == "ungrouped_norm" else G, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + c["layer_norm_epsilon"])
    return mm(y.reshape(B, S, H * P) * p["gate_norm"].astype(F32), p["w_out"]), kept, tail


def attention(u, p, c: dict, mm, q_):
    """``u`` [B, S, D] normed -> ``(out [B, S, D], each position's ``[k; v]``
    [B, S, 2 KH Dh])``; ``q_`` rounds the operands of the two products that
    are not with a weight."""
    Hq, KH, Dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    B, S, _ = u.shape
    q = mm(u, p["wq"]).reshape(B, S, KH, Hq // KH, Dh)
    k, v = mm(u, p["wk"]), mm(u, p["wv"])
    kv = jnp.concatenate([k, v], axis=-1)
    k, v = k.reshape(B, S, KH, Dh), v.reshape(B, S, KH, Dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q_(q), q_(k)) * Dh**-0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None, None], s, -jnp.inf)
    a = jnp.einsum("bkgqs,bskd->bqkgd", q_(jax.nn.softmax(s, axis=-1)), q_(v))
    return mm(a.reshape(B, S, Hq * Dh), p["wo"]), kv


def forward(weights: dict, tokens, c: dict, quant=None, inner: bool = False,
            keep_at=None, wrong=None):
    """tokens [B, S] -> logits [B, S, vocab] float32, the whole sequence at
    once with no cache; with ``inner``, also ``{"picks": the chosen experts [E
    blocks, B, S, k], "kv": each position's keys and values ``[k; v]`` [*
    blocks, B, S, 2 KH Dh], "state" [M blocks, B, H, P, N] and "conv" [M blocks,
    B, K - 1, H P + 2 G N]: the recurrent state after ``keep_at[b]`` tokens of
    sequence b (default: all) and the pre-convolution rows before that
    position}``."""
    assert wrong in (None, *WRONGS), wrong
    q_ = quantizer(quant)
    eps = c["layer_norm_epsilon"]
    B, S = tokens.shape
    keep_at = jnp.full((B,), S, jnp.int32) if keep_at is None else jnp.asarray(keep_at, jnp.int32)

    def mm(a, w):
        return q_(a) @ q_(w.astype(F32))

    chosen, kvs, states, tails = [], [], [], []
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][tokens].astype(F32)
        for kind, p in zip(c["hybrid_override_pattern"], weights["layers"]):
            u = _rms_norm(x, p["norm"], eps)
            if kind == "M":
                y, state, tail = mamba(u, p, c, mm, keep_at, wrong)
                states.append(state)
                tails.append(tail)
            elif kind == "*":
                y, kv = attention(u, p, c, mm, q_)
                kvs.append(kv)
            else:
                assert kind == "E", kind
                y, idx = experts(u, p, c, mm, wrong)
                chosen.append(idx)
            x = x + y
        logits = mm(_rms_norm(x, weights["final_norm"], eps), weights["lm_head"])
    if inner:
        stack = lambda a: jnp.stack(a) if a else None  # noqa: E731
        return logits, {"picks": stack(chosen), "kv": stack(kvs),
                        "state": stack(states), "conv": stack(tails)}
    return logits
