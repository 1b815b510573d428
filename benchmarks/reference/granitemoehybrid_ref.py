"""Plain reference of the Granite 4.0-H decoder (granite-4.0-h-micro,
``model_type: granitemoehybrid``).

Published description (the model's ``config.json`` and the family's modelling
code; Mamba-2, arXiv:2405.21060), ``config`` keys in brackets:

- ``x_0 = embedding_multiplier E[token]``. RMSNorm with ``rms_norm_eps``. Every
  layer: ``x += residual_multiplier Mixer(RMSNorm_1(x))``, then ``x +=
  residual_multiplier MLP(RMSNorm_2(x))``. Final RMSNorm; the head is the
  embedding [``tie_word_embeddings``]: ``logits = RMSNorm(x) E^T /
  logits_scaling``. No biases but the convolution's.
- ``Mixer`` of layer ``l`` by ``layer_types[l]``.
- **mamba** (H = ``mamba_n_heads`` heads of P = ``mamba_d_head``, state N =
  ``mamba_d_state``, G = ``mamba_n_groups`` groups, K = ``mamba_d_conv``):
  ``[z | xBC | dt] = W_in u`` of widths ``H P | H P + 2 G N | H``; ``xBC =
  silu(conv_K(xBC) + b)``, causal and depthwise, zeros before the start; split
  into ``x`` [H, P], ``B`` and ``C`` [G, N]; ``dt = softplus(dt + dt_bias)``, ``A
  = -exp(A_log)``; ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t
  C_t + D x_t`` on a float32 state [H, P, N], zero at the start; ``g = y
  silu(z)``; RMSNorm of ``g`` over each of the G groups of channels (one group:
  all of them) times its scale; ``W_out``. Token by token, a plain ``lax.scan``
  over positions.
- **attention**: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``hidden_size /
  num_attention_heads``; no rotation and no other position signal
  [``position_embedding_type: nope``]; ``s_ij = (q_i . k_j)
  attention_multiplier`` (the multiplier replaces ``head^-1/2``); causal
  softmax; ``W_o``. No cache.
- **MLP**: ``W_down (silu(W_gate m) * W_up m)`` at ``shared_intermediate_size``
  (``num_local_experts: 0``: there is no routed part), gate and up the two
  halves of one matrix.

This file draws no weights: the output check hands ``forward`` the ones the
served program drew from the seed, as ``ray_tpu.models.granite_hybrid`` lays
them out (``weights["period"][place][name][turn]``: the parameters of like
places of the layer pattern's period stacked over its turns). Layer ``l`` is
place ``l % len(period)`` of turn ``l // len(period)``. Imports nothing of the
program.

``wrong`` names one departure from the mathematics above, for the output
check's controls: ``sqrt_scale`` (scores times ``head^-1/2``),
``no_residual_multiplier``, ``no_embedding_multiplier``, ``unscaled_logits``,
``untied`` (a head drawn apart from the embedding), ``eight_groups`` (the gated
norm over eight groups of channels).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.common import F32, quantizer
from benchmarks.reference.nemotron_h_ref import ssm_recurrence  # the token-by-token scan: the same Mamba-2 recurrence

WRONGS = ("sqrt_scale", "no_residual_multiplier", "no_embedding_multiplier", "unscaled_logits",
          "untied", "eight_groups")


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def period_of(layer_types) -> int:
    """The length of the shortest unit that ``layer_types`` repeats."""
    kinds = list(layer_types)
    return next(n for n in range(1, len(kinds) + 1)
                if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n))


def mamba(u, p, c: dict, mm, keep_at, wrong=None):
    """``u`` [B, S, D] normed -> ``(out [B, S, D], the state [B, H, P, N] after
    ``keep_at[b]`` tokens of sequence b, the K - 1 pre-convolution rows before
    that position [B, K - 1, H P + 2 G N])``."""
    H, P, G, N, K = (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_n_groups"],
                     c["mamba_d_state"], c["mamba_d_conv"])
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
    y = y.reshape(B, S, 8 if wrong == "eight_groups" else G, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + c["rms_norm_eps"])
    return mm(y.reshape(B, S, H * P) * p["gate_norm"].astype(F32), p["w_out"]), kept, tail


def attention(u, p, c: dict, mm, q_, wrong=None):
    """``u`` [B, S, D] normed -> ``(out [B, S, D], each position's ``[k; v]``
    [B, S, 2 KH Dh])``; ``q_`` rounds the operands of the two products that
    are not with a weight."""
    Hq, KH = c["num_attention_heads"], c["num_key_value_heads"]
    Dh = c["hidden_size"] // Hq
    B, S, _ = u.shape
    q = mm(u, p["wq"]).reshape(B, S, KH, Hq // KH, Dh)
    k, v = mm(u, p["wk"]), mm(u, p["wv"])
    kv = jnp.concatenate([k, v], axis=-1)
    k, v = k.reshape(B, S, KH, Dh), v.reshape(B, S, KH, Dh)
    scale = Dh**-0.5 if wrong == "sqrt_scale" else c["attention_multiplier"]
    s = jnp.einsum("bqkgd,bskd->bkgqs", q_(q), q_(k)) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None, None], s, -jnp.inf)
    a = jnp.einsum("bkgqs,bskd->bqkgd", q_(jax.nn.softmax(s, axis=-1)), q_(v))
    return mm(a.reshape(B, S, Hq * Dh), p["wo"]), kv


def mlp(m, p, mm):
    gate, up = jnp.split(mm(m, p["w_gate_up"]), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, p["w_down"])


def forward(weights: dict, tokens, c: dict, quant=None, inner: bool = False,
            keep_at=None, wrong=None, logits_at=None):
    """tokens [B, S] -> logits [B, S, vocab] float32 (with ``logits_at`` [B, n]
    int: of those positions of each sequence only, [B, n, vocab]), the whole
    sequence at once with no cache; with ``inner``, also ``{"kv": each
    position's keys and values ``[k; v]`` [attention layers, B, S, 2 KH Dh],
    "state" [mamba layers, B, H, P, N] and "conv" [mamba layers, B, K - 1, H P
    + 2 G N]: the recurrent state after ``keep_at[b]`` tokens of sequence b
    (default: all) and the pre-convolution rows before that position}``."""
    assert wrong in (None, *WRONGS), wrong
    q_ = quantizer(quant)
    eps = c["rms_norm_eps"]
    B, S = tokens.shape
    keep_at = jnp.full((B,), S, jnp.int32) if keep_at is None else jnp.asarray(keep_at, jnp.int32)
    r = 1.0 if wrong == "no_residual_multiplier" else c["residual_multiplier"]
    n = period_of(c["layer_types"])

    def mm(a, w):
        return q_(a) @ q_(w.astype(F32))

    kvs, states, tails = [], [], []
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][tokens].astype(F32)
        if wrong != "no_embedding_multiplier":
            x = x * c["embedding_multiplier"]
        for l, kind in enumerate(c["layer_types"]):
            p = jax.tree.map(lambda a: a[l // n], weights["period"][l % n])
            u = _rms_norm(x, p["norm"], eps)
            if kind == "mamba":
                y, state, tail = mamba(u, p, c, mm, keep_at, wrong)
                states.append(state)
                tails.append(tail)
            else:
                assert kind == "attention", kind
                y, kv = attention(u, p, c, mm, q_, wrong)
                kvs.append(kv)
            x = x + r * y
            x = x + r * mlp(_rms_norm(x, p["mlp_norm"], eps), p, mm)
        if logits_at is not None:
            x = jnp.take_along_axis(x, jnp.asarray(logits_at, jnp.int32)[:, :, None], axis=1)
        head = weights["wte"]
        if wrong == "untied":  # a head of the embedding's scale, drawn apart from it
            head = jax.random.normal(jax.random.key(0), head.shape, F32) * jnp.std(head.astype(F32))
        logits = mm(_rms_norm(x, weights["final_norm"], eps), head.T)
        if wrong != "unscaled_logits":
            logits = logits / c["logits_scaling"]
    if inner:
        return logits, {"kv": jnp.stack(kvs), "state": jnp.stack(states), "conv": jnp.stack(tails)}
    return logits
