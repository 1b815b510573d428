"""Plain reference of the decoder with latent attention in every layer and
group-limited routed experts (A.X-K1, ``model_type: axk1``; the layer equations
are those of the DeepSeek-V3 family's published modelling code, whose keys the
configuration uses).

Token embedding; per block ``x += MLA(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``,
layers numbered from 1; final RMSNorm; untied head. The feed-forward is a dense
SwiGLU in the first ``first_k_dense_replace`` layers and a routed mixture with
``n_shared_experts`` shared experts in the others (``moe_layer_freq`` 1).

- **MLA** (H heads, ``d_n = qk_nope_head_dim``, ``d_r = qk_rope_head_dim``,
  ``d_v = v_head_dim``, no biases): ``c_q = RMSNorm(h W_dq)``, ``q = c_q W_uq``
  -> per head ``[q_n; q_r]``; ``[c; k_r] =
  h W_dkv`` with ``k_r`` shared by all heads, ``c^ = RMSNorm(c)``, per head
  ``[k_n; v] = c^ W_ukv``; scores ``(q_n . k_n + R_t q_r . R_s k_r) * scale``,
  causal softmax, values, ``W_o``. Unabsorbed, no cache, every position at once.
- **Rotation**: ``R_t`` rotates the pairs ``(2i, 2i + 1)`` of the ``d_r`` rope
  dims by ``t f_i`` (the family's interleaved convention). YaRN: ``f_i = (1 -
  m_i) theta^(-2i/d_r) / factor + m_i theta^(-2i/d_r)``, ``m_i = 1 - clip((i -
  low) / (high - low), 0, 1)``, ``low, high = floor, ceil of d_r ln(original /
  (beta 2 pi)) / (2 ln theta)`` at ``beta_fast``, ``beta_slow``. cos and sin
  are multiplied by ``ym(factor, mscale) / ym(factor, mscale_all_dim)`` and
  ``scale = (d_n + d_r)^-1/2 ym(factor, mscale_all_dim)^2``, ``ym(s, m) = 0.1 m
  ln s + 1``.
- **Experts**: ``s = sigmoid(h W_r)`` over all routed experts of the model, in
  float32; groups are ``n_group`` runs of consecutive experts; a group's score
  is the sum of its two largest ``s``; the ``topk_group`` best groups stay; the
  top ``num_experts_per_tok`` of their experts are chosen; weights ``s`` of the
  chosen over their sum (``norm_topk_prob``) times ``routed_scaling_factor``;
  the result is the sum over the chosen experts *that are held here*
  (``n_routed_experts`` of them from ``expert_offset``;
  ``published.n_routed_experts`` is the router's width) plus the shared expert;
  ``E(h) = W_down(SiLU(W_gate h) * W_up h)``. A masked loop over the experts
  held; what the absent ones would add is left out, as in the program.

Departures, each also under ``assumed`` in the configuration's file:
``topk_method: "none"`` with ``seq_aux`` is read as "no selection bias
(``e_score_correction_bias``) exists", so the router has weights only; the
group score is the sum of the top two (V3's code; V2's took the maximum); an
expert outside the kept groups is masked with minus infinity where the
published code writes 0.0, which is the same thing for scores in (0, 1) and no
bias; attention is computed a few heads at a time (the same numbers, less
memory); weights are random. This file draws no weights: the output check
hands ``forward`` the ones the served program drew from the seed (one dict a
layer, as ``ray_tpu.models.mla_moe`` names them; ``assumed.router`` says how
the routers were drawn and why the reference cannot draw its own).

``variant`` computes a wrong model on purpose, for the output check's
controls: ``"norope"`` leaves the rotation out, ``"noyarn"`` rotates by the
plain frequencies and scales by the plain ``(d_n + d_r)^-1/2``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.common import F32, quantizer

HEADS_AT_ONCE = 8  # [B, 8, S, S] float32 scores are 0.27 GB at two sequences of 2,043


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def frequencies(c: dict, variant=None):
    """``f_i``, i = 0 .. d_r/2 - 1, and the two scalars: what cos and sin are
    multiplied by, and the softmax scale."""
    d_r, theta = c["qk_rope_head_dim"], float(c["rope_theta"])
    plain = theta ** (-2.0 * jnp.arange(d_r // 2, dtype=F32) / d_r)
    scale = (c["qk_nope_head_dim"] + d_r) ** -0.5
    rs = c.get("rope_scaling")
    if rs is None or variant == "noyarn":
        return plain, 1.0, scale
    assert rs["type"] == "yarn", rs

    def turns_at(beta):
        return d_r * math.log(rs["original_max_position_embeddings"] / (beta * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_at(rs["beta_fast"])), 0)
    high = min(math.ceil(turns_at(rs["beta_slow"])), d_r - 1)
    m = 1.0 - jnp.clip((jnp.arange(d_r // 2, dtype=F32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    all_dim = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (
        (1.0 - m) * plain / rs["factor"] + m * plain,
        yarn_mscale(rs["factor"], rs["mscale"]) / all_dim,
        scale * all_dim * all_dim,
    )


def rotate(x, angles, mscale):
    """Pairs ``(2i, 2i + 1)`` of the last axis by ``angles`` [S, d_r/2]; ``x``
    is [B, S, ..., d_r] with any axes between."""
    mid = (1,) * (x.ndim - 3)
    cos = (jnp.cos(angles) * mscale).reshape(1, angles.shape[0], *mid, -1)
    sin = (jnp.sin(angles) * mscale).reshape(1, angles.shape[0], *mid, -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def route(h, p, c: dict, mm):
    """Chosen experts [..., k] and their weights, over all routed experts."""
    s = jax.nn.sigmoid(mm(h, p["router"]))
    choice = s + p["router_bias"] if "router_bias" in p else s
    G = c["n_group"]
    if G > 1:
        groups = choice.reshape(*choice.shape[:-1], G, -1)
        top2 = jnp.sum(jnp.sort(groups, axis=-1)[..., -2:], axis=-1)  # [..., G]
        kth = jnp.sort(top2, axis=-1)[..., G - c["topk_group"]]
        # (the program breaks a tie between groups by the lower index; exact ties
        # of float32 sums of sigmoids do not occur on random weights)
        keep = top2 >= kth[..., None]
        choice = jnp.where(keep[..., None], groups, -jnp.inf).reshape(choice.shape)
    _, idx = jax.lax.top_k(choice, c["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if c["norm_topk_prob"]:
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


def forward(weights: dict, tokens, c: dict, quant=None, inner: bool = False, variant=None):
    """tokens [B, S] -> logits [B, S, vocab] float32, the whole sequence at
    once with no cache; with ``inner``, also ``{"picks": the chosen experts
    [expert layers, B, S, k], "latents": the rows [c^; R_t k_r] that a latent
    cache would hold [layers, B, S, rank + d_r]}``."""
    q_ = quantizer(quant)
    H, dn, dr, dv, R = (c["num_attention_heads"], c["qk_nope_head_dim"],
                        c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"])
    eps = c["rms_norm_eps"]
    B, S = tokens.shape
    causal = jnp.tril(jnp.ones((S, S), bool))
    freqs, mscale, scale = frequencies(c, variant)
    angles = jnp.arange(S, dtype=F32)[:, None] * freqs[None, :]
    turn = (lambda x: x) if variant == "norope" else (lambda x: rotate(x, angles, mscale))
    hg = min(HEADS_AT_ONCE, H)
    assert H % hg == 0

    def mm(a, w):
        return q_(a) @ q_(w.astype(F32))

    def mla(h, p):
        q = mm(_rms_norm(mm(h, p["wq_a"]), p["q_norm"], eps), p["wq_b"]).reshape(B, S, H, dn + dr)
        q = jnp.concatenate([q[..., :dn], turn(q[..., dn:])], axis=-1)
        ckv = mm(h, p["wkva"])
        c_hat, k_r = _rms_norm(ckv[..., :R], p["kv_norm"], eps), turn(ckv[..., R:])
        latents.append(jnp.concatenate([c_hat, k_r], axis=-1))
        kv = mm(c_hat, p["wkvb"]).reshape(B, S, H, dn + dv)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r[:, :, None], (B, S, H, dr))], axis=-1)

        def heads(qkv):  # a few heads at a time: [B, S, hg, d] each
            qh, kh, vh = qkv
            s = jnp.einsum("bqhd,bkhd->bhqk", q_(qh), q_(kh)) * scale
            s = jnp.where(causal[None, None], s, -jnp.inf)
            return jnp.einsum("bhqk,bkhd->bqhd", q_(jax.nn.softmax(s, axis=-1)), q_(vh))

        split = lambda a: jnp.moveaxis(a.reshape(B, S, H // hg, hg, -1), 2, 0)  # noqa: E731
        a = jax.lax.map(heads, (split(q), split(k), split(kv[..., dn:])))  # [H/hg, B, S, hg, dv]
        return mm(jnp.moveaxis(a, 0, 2).reshape(B, S, H * dv), p["wo"])

    chosen, latents = [], []
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][tokens].astype(F32)
        for i, p in enumerate(weights["layers"], start=1):
            x = x + mla(_rms_norm(x, p["attn_norm"], eps), p)
            h = _rms_norm(x, p["mlp_norm"], eps)
            if i > c["first_k_dense_replace"]:
                y, idx = moe(h, p, c, mm)
                chosen.append(idx)
            else:
                y = mm(jax.nn.silu(mm(h, p["w_gate"])) * mm(h, p["w_up"]), p["w_down"])
            x = x + y
        logits = mm(_rms_norm(x, weights["final_norm"], eps), weights["lm_head"])
    if inner:
        return logits, {"picks": jnp.stack(chosen), "latents": jnp.stack(latents)}
    return logits
