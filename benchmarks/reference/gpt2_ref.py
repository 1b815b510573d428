"""Plain reference of GPT-2 (Radford et al. 2019; Hugging Face
``GPT2LMHeadModel``): token plus learned position embedding; per block
LayerNorm -> multi-head causal attention (fused QKV with bias, output
projection with bias) -> residual, LayerNorm -> MLP with ``gelu_new`` (the
tanh approximation) -> residual; final LayerNorm; output head tied to the
token embedding. Loss is the mean next-token cross-entropy.

Departures, each also under ``assumed`` in the configuration's file: the
vocabulary is held padded to 50304 rows and all rows enter the softmax (as
in the program); no dropout; weights are random. ``jax.checkpoint`` around
the block only bounds the memory of the float32 backward pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.common import F32, INIT_STD, fold, normal, quantizer


def init_weights(seed: int, c: dict) -> dict:
    """Stacked-layer pytree as ``ray_tpu.models.gpt2`` reads it."""
    dt = jnp.dtype(c["param_dtype"])
    L, D, S = c["n_layer"], c["n_embd"], c["n_positions"]
    F = c.get("n_inner") or 4 * D
    V = c["assumed"]["padded_vocab_size"]
    resid = INIT_STD / (2 * L) ** 0.5
    k = iter(jax.random.split(fold(seed), 6))
    w = lambda shape, std=INIT_STD: normal(next(k), std, shape, dt)  # noqa: E731
    zeros, ones = (lambda *s: jnp.zeros(s, dt)), (lambda *s: jnp.ones(s, dt))
    return {
        "wte": w((V, D)),
        "wpe": w((S, D)),
        "blocks": {
            "ln1_scale": ones(L, D), "ln1_bias": zeros(L, D),
            "qkv_w": w((L, D, 3 * D)), "qkv_b": zeros(L, 3 * D),
            "proj_w": w((L, D, D), resid), "proj_b": zeros(L, D),
            "ln2_scale": ones(L, D), "ln2_bias": zeros(L, D),
            "fc_w": w((L, D, F)), "fc_b": zeros(L, F),
            "fc2_w": w((L, F, D), resid), "fc2_b": zeros(L, D),
        },
        "lnf_scale": ones(D), "lnf_bias": zeros(D),
    }


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh((2.0 / jnp.pi) ** 0.5 * (x + 0.044715 * x**3)))


def forward(weights: dict, tokens, c: dict, quant=None):
    """tokens [B, S] -> logits [B, S, padded vocab], float32."""
    q_ = quantizer(quant)
    H, eps = c["n_head"], c["layer_norm_epsilon"]
    B, S = tokens.shape
    D = c["n_embd"]
    Dh = D // H
    causal = jnp.tril(jnp.ones((S, S), bool))

    def mm(a, w):
        return q_(a) @ q_(w.astype(F32))

    @jax.checkpoint
    def block(x, p):
        p = jax.tree.map(lambda t: t.astype(F32), p)
        h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"], eps)
        qkv = mm(h, p["qkv_w"]) + p["qkv_b"]
        q, k, v = (t.reshape(B, S, H, Dh) for t in jnp.split(qkv, 3, axis=-1))
        s = jnp.einsum("bqhd,bkhd->bhqk", q_(q), q_(k)) / Dh**0.5
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", q_(jax.nn.softmax(s, axis=-1)), q_(v))
        x = x + mm(a.reshape(B, S, D), p["proj_w"]) + p["proj_b"]
        h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"], eps)
        h = _gelu_new(mm(h, p["fc_w"]) + p["fc_b"])
        return x + mm(h, p["fc2_w"]) + p["fc2_b"], None

    with jax.default_matmul_precision("highest"):
        wte = weights["wte"].astype(F32)
        x = wte[tokens] + weights["wpe"].astype(F32)[:S][None]
        x, _ = jax.lax.scan(block, x, weights["blocks"])
        x = _layer_norm(
            x, weights["lnf_scale"].astype(F32), weights["lnf_bias"].astype(F32), eps
        )
        return mm(x, wte.T)


def loss(weights: dict, batch: dict, c: dict, quant=None):
    """Mean cross-entropy of ``batch["targets"]`` under the logits."""
    logits = forward(weights, batch["tokens"], c, quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1)
    return -jnp.mean(picked)


def loss_and_grads(weights: dict, batch: dict, c: dict, quant=None):
    return jax.value_and_grad(loss)(weights, batch, c, quant)
