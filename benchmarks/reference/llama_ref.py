"""Plain reference of the Llama-family decoder that Mistral-7B-v0.3 is.

Published description (Mistral 7B, arXiv:2310.06825, and the Hugging Face
``MistralForCausalLM``): token embedding; per block RMSNorm -> grouped-query
attention with rotary position embeddings -> residual, RMSNorm -> SwiGLU
(``down(silu(gate(x)) * up(x))``) -> residual; final RMSNorm; untied output
head. No biases. v0.3 has no sliding window.

Departures, each also under ``assumed`` in the configuration's file: rotary
pairs are the half-split ones of Hugging Face (dimension i with i + head/2);
weights are random.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.common import F32, INIT_STD, fold, normal, quantizer


def init_weights(seed: int, c: dict) -> dict:
    """Stacked-layer pytree as ``ray_tpu.models.llama`` reads it."""
    dt = jnp.dtype(c["param_dtype"])
    L, D, F, V = (c["num_hidden_layers"], c["hidden_size"],
                  c["intermediate_size"], c["vocab_size"])
    Q = c["num_attention_heads"] * c["head_dim"]
    KV = c["num_key_value_heads"] * c["head_dim"]
    resid = INIT_STD / (2 * L) ** 0.5
    k = iter(jax.random.split(fold(seed), 9))
    w = lambda shape, std=INIT_STD: normal(next(k), std, shape, dt)  # noqa: E731
    return {
        "wte": w((V, D)),
        "blocks": {
            "attn_norm": jnp.ones((L, D), dt),
            "wq": w((L, D, Q)),
            "wk": w((L, D, KV)),
            "wv": w((L, D, KV)),
            "wo": w((L, Q, D), resid),
            "mlp_norm": jnp.ones((L, D), dt),
            "w_gate": w((L, D, F)),
            "w_up": w((L, D, F)),
            "w_down": w((L, F, D), resid),
        },
        "final_norm": jnp.ones((D,), dt),
        "lm_head": w((D, V)),
    }


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(t, theta):
    """t [B, S, H, Dh]; position s rotates pair (i, i + Dh/2) by
    s * theta^(-2i/Dh)."""
    S, half = t.shape[1], t.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def forward(weights: dict, tokens, c: dict, quant=None):
    """tokens [B, S] -> logits [B, S, vocab], float32, full causal attention
    over the whole sequence (no cache)."""
    q_ = quantizer(quant)
    H, KH, Dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    B, S = tokens.shape
    causal = jnp.tril(jnp.ones((S, S), bool))

    def mm(a, w):
        return q_(a) @ q_(w.astype(F32))

    def block(x, p):
        h = _rms_norm(x, p["attn_norm"].astype(F32), eps)
        q = _rope(mm(h, p["wq"]).reshape(B, S, H, Dh), theta)
        k = _rope(mm(h, p["wk"]).reshape(B, S, KH, Dh), theta)
        v = mm(h, p["wv"]).reshape(B, S, KH, Dh)
        k = jnp.repeat(k, H // KH, axis=2)  # each key/value head serves a group
        v = jnp.repeat(v, H // KH, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_(q), q_(k)) / Dh**0.5
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", q_(jax.nn.softmax(s, axis=-1)), q_(v))
        x = x + mm(a.reshape(B, S, H * Dh), p["wo"])
        h = _rms_norm(x, p["mlp_norm"].astype(F32), eps)
        x = x + mm(jax.nn.silu(mm(h, p["w_gate"])) * mm(h, p["w_up"]), p["w_down"])
        return x, None

    with jax.default_matmul_precision("highest"):
        x = weights["wte"][tokens].astype(F32)
        x, _ = jax.lax.scan(block, x, weights["blocks"])
        x = _rms_norm(x, weights["final_norm"].astype(F32), eps)
        return mm(x, weights["lm_head"])
