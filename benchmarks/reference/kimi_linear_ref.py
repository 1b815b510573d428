"""Plain reference of the Kimi Linear decoder (Kimi-Linear-48B-A3B-Instruct).

Published description (Kimi Linear, arXiv:2510.26692, and the model's
``config.json`` and modelling code, ``model_type: kimi_linear``): token
embedding; per block ``x += Mixer(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``;
final RMSNorm; untied head. Layers are numbered from 1. The mixer is MLA at
``linear_attn_config.full_attn_layers`` and KDA at ``kda_layers``; the
feed-forward is a dense SwiGLU in the first ``first_k_dense_replace`` layers
and a routed mixture of experts with one shared expert in the others.

- **KDA** (H heads of d_k = d_v = ``linear_attn_config.head_dim``): ``q~, k~,
  v~ = W_q h, W_k h, W_v h``, each through its own causal depthwise
  convolution of width 4 and SiLU; per head ``q = q'/|q'| d_k^-1/2``, ``k =
  k'/|k'|``; log decay per head and key channel ``g = -exp(A_log) softplus(
  W_f_up W_f_down h + dt_bias)``, ``alpha = exp(g)``; ``beta = sigmoid(W_b
  h)``; state ``S`` [d_k, d_v], zero at the start: ``S' = Diag(alpha_t)
  S_{t-1}``, ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S_t^T q_t``;
  output ``W_o(RMSNorm_head(o_t) * sigmoid(W_g_up W_g_down h))``. Computed
  here token by token (``lax.scan`` over the sequence), not by chunks.
- **MLA** (``q_lora_rank`` null): ``q = W_q h`` -> per head ``[q_nope; q_pe]``;
  ``[c; k_pe] = W_kva h`` with ``k_pe`` shared by all heads, ``c^ = RMSNorm(c)``,
  per head ``[k_nope; v] = W_kvb c^``; no rotation (``mla_use_nope``); causal
  softmax of ``q.k / sqrt(d_nope + d_pe)``; ``W_o``. Unabsorbed, no cache.
- **Experts**: ``s = sigmoid(W_r h)`` over all routed experts of the model, in
  float32; the ``num_experts_per_token`` are chosen by ``s + bias``; weights
  ``routed_scaling_factor * s_i / sum of the chosen s``; the result is the sum
  over the chosen experts *that are held here* (``num_experts`` of them from
  ``expert_offset``; ``published.num_experts`` is the router's width) plus the
  shared expert; ``E(h) = W_down(SiLU(W_gate h) * W_up h)``. A masked loop over
  the experts held; what the absent ones would add is left out, as in the
  program.

Departures, each also under ``assumed`` in the configuration's file: the rank
of the decay's and the output gate's low-rank pairs, ``A_log`` per head and
``dt_bias`` per key channel (in the modelling code, not in ``config.json``);
the normalisation of ``q`` and ``k`` adds 1e-6 under the root; the state is
float32; weights are random, the router's drawn so that its logits have unit
variance and kept in float32, ``e_score_correction_bias`` balanced. This file
draws no weights: the output check hands ``forward`` the ones the served
program drew from the seed (one dict a layer, as ``ray_tpu.models.kimi_linear``
names them; the configuration's ``assumed.router`` says why not its own).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.common import F32, quantizer


def layer_kinds(c: dict) -> list:
    """(mixer, feed-forward) of layers 1..num_hidden_layers."""
    full = set(c["linear_attn_config"]["full_attn_layers"])
    return [
        ("mla" if i in full else "kda", "dense" if i <= c["first_k_dense_replace"] else "moe")
        for i in range(1, c["num_hidden_layers"] + 1)
    ]


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def kda_recurrence(q, k, v, g, beta, S0):
    """Token by token. ``q, k, g`` [B, S, H, d_k], ``v`` [B, S, H, d_v],
    ``beta`` [B, S, H], ``S0`` [B, H, d_k, d_v] -> ``(o [B, S, H, d_v], S)``."""

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = jnp.exp(g_t)[..., None] * S  # Diag(alpha_t) S
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    S, o = jax.lax.scan(step, S0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


def route(h, p, c: dict, mm):
    """Chosen experts [..., k] and their weights, over all routed experts."""
    s = jax.nn.sigmoid(mm(h, p["router"]))
    _, idx = jax.lax.top_k(s + p["router_bias"], c["num_experts_per_token"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if c["moe_renormalize"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * c["routed_scaling_factor"]


def moe(h, p, c: dict, mm):
    """The experts held here on their picks, plus the shared expert; also
    the picks."""
    idx, w = route(h, p, c, mm)
    swiglu = lambda a, g, u, d: mm(jax.nn.silu(mm(a, g)) * mm(a, u), d)  # noqa: E731

    def one(y, e):
        n, gate, up, down = e
        w_e = jnp.sum(jnp.where(idx == n + c["expert_offset"], w, 0.0), axis=-1)
        return y + w_e[..., None] * swiglu(h, gate, up, down), None

    held = jnp.arange(p["e_gate"].shape[0])
    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (held, p["e_gate"], p["e_up"], p["e_down"]))
    return y + swiglu(h, p["s_gate"], p["s_up"], p["s_down"]), idx


def forward(weights: dict, tokens, c: dict, quant=None, inner: bool = False):
    """tokens [B, S] -> logits [B, S, vocab] float32, the whole sequence at
    once with no cache; with ``inner``, also ``{"picks": the chosen experts
    [expert layers, B, S, k], "latents": the rows [c^; k_pe] that a latent
    cache would hold [MLA layers, B, S, rank + d_pe]}``."""
    q_ = quantizer(quant)
    la = c["linear_attn_config"]
    H, d, K = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    Hm, dn, dp, dv, R = (c["num_attention_heads"], c["qk_nope_head_dim"],
                         c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"])
    eps = c["rms_norm_eps"]
    B, S = tokens.shape
    causal = jnp.tril(jnp.ones((S, S), bool))

    def mm(a, w):
        return q_(a) @ q_(w.astype(F32))

    def kda(h, p):
        x = jnp.pad(mm(h, p["wqkv"]), ((0, 0), (K - 1, 0), (0, 0)))  # zeros before the start
        conv = p["conv"].astype(F32)
        mixed = jax.nn.silu(sum(conv[j] * x[:, j : j + S] for j in range(K)))
        q, k, v = (a.reshape(B, S, H, d) for a in jnp.split(mixed, 3, axis=-1))
        l2 = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
        f = mm(mm(h, p["f_down"]), p["f_up"]) + p["dt_bias"]
        g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(f.reshape(B, S, H, d))
        beta = jax.nn.sigmoid(mm(h, p["wb"]))
        o, _ = kda_recurrence(l2(q) * d**-0.5, l2(k), v, g, beta,
                              jnp.zeros((B, H, d, d), F32))
        gate = jax.nn.sigmoid(mm(mm(h, p["g_down"]), p["g_up"]))
        o = _rms_norm(o, p["o_norm"], eps).reshape(B, S, H * d) * gate
        return mm(o, p["wo"])

    def mla(h, p):
        q = mm(h, p["wq"]).reshape(B, S, Hm, dn + dp)
        ckv = mm(h, p["wkva"])
        c_hat = _rms_norm(ckv[..., :R], p["kv_norm"], eps)
        latents.append(jnp.concatenate([c_hat, ckv[..., R:]], axis=-1))
        k_pe = jnp.broadcast_to(ckv[..., None, R:], (B, S, Hm, dp))
        kv = mm(c_hat, p["wkvb"]).reshape(B, S, Hm, dn + dv)
        k = jnp.concatenate([kv[..., :dn], k_pe], axis=-1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_(q), q_(k)) / (dn + dp) ** 0.5
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", q_(jax.nn.softmax(s, axis=-1)), q_(kv[..., dn:]))
        return mm(a.reshape(B, S, Hm * dv), p["wo"])

    chosen, latents = [], []
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][tokens].astype(F32)
        for (mixer, ffn), p in zip(layer_kinds(c), weights["layers"]):
            h = _rms_norm(x, p["attn_norm"], eps)
            x = x + (mla(h, p) if mixer == "mla" else kda(h, p))
            h = _rms_norm(x, p["mlp_norm"], eps)
            if ffn == "moe":
                y, idx = moe(h, p, c, mm)
                chosen.append(idx)
            else:
                y = mm(jax.nn.silu(mm(h, p["w_gate"])) * mm(h, p["w_up"]), p["w_down"])
            x = x + y
        logits = mm(_rms_norm(x, weights["final_norm"], eps), weights["lm_head"])
    if inner:
        return logits, {"picks": jnp.stack(chosen), "latents": jnp.stack(latents)}
    return logits
