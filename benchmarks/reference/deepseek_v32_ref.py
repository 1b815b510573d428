"""Plain reference of DeepSeek-V3.2-Exp's decoder (``model_type:
deepseek_v32``): the DeepSeek-V3 block of ``mla_moe_ref.py`` (whose rotation,
router and expert layer this file imports: the same equations) with the
lightning indexer of the published inference code (``inference/model.py``,
class ``Indexer``) and of the report (*DeepSeek-V3.2-Exp: Boosting
Long-Context Efficiency with DeepSeek Sparse Attention*, eq. 1-2) in front of
its attention.

In every layer, with ``h = RMSNorm(x)`` and ``c_q = RMSNorm(h W_dq)``:

1. ``qI = c_q W_Iq`` [S, J, d_I]; ``kI = LayerNorm(h W_Ik)`` [S, d_I], one key
   a position for all index heads, LayerNorm with weight and bias; ``w = (h
   W_Iw) J^-1/2 d_I^-1/2`` [S, J];
2. the first ``qk_rope_head_dim`` values of ``qI`` and ``kI`` are rotated by
   the position with the YaRN frequencies of MLA's rope part, in halves
   (pairs ``(i, i + d_r / 2)``; MLA's shared key rotates pairs ``(2i, 2i + 1)``);
3. ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``, ``s <= t``;
4. ``S_t``: the positions of the ``min(index_topk, t + 1)`` largest ``I[t, :t +
   1]``, a tie to the lower position (``lax.top_k``'s order);
5. MLA per head over expanded keys and values, softmax and weighted sum over
   ``s in S_t`` only.

Float32, matrix products at ``highest`` precision, no cache, no absorption,
no kernels; one sequence at a time, queries in blocks and heads in groups so
that 16k positions fit beside the weights. It is given the chip's share
(``n_routed_experts`` experts from ``expert_offset``, the sliced vocabulary)
and the weights the served program drew (``assumed.router`` in the
configuration's file says why it cannot draw its own).

Departures, each under ``assumed`` in the configuration's file: the published
code rotates ``qI`` and ``kI`` by a Hadamard matrix and keeps ``kI`` in FP8
with a scale a row; the rotation is orthogonal and on both sides of the dot
product, so with keys in bfloat16 it changes no score and is left out; the
multi-token-prediction module is left out (the published inference code has
none either).

``variant`` computes a wrong model on purpose, for the output check's
controls: ``"dense"`` leaves step 4 out (every position ``s <= t`` is
attended), ``"recent"`` keeps the newest ``index_topk`` positions in place of
the top. ``quant`` rounds every matrix product's operands (``fp8``, ``bf16``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.common import F32, quantizer
from benchmarks.reference.mla_moe_ref import _rms_norm, frequencies, moe, rotate

HEADS_AT_ONCE = 8
QUERIES_AT_ONCE = 128  # [128, J, S] float32 index products are 0.54 GB at 64 heads and 16k positions
ROWS_AT_ONCE = 2048  # of the dense feed-forward: [2048, 18432] float32 twice


def rotate_halves(x, angles):
    """Pairs ``(i, i + d / 2)`` of the first ``d = 2 angles.shape[-1]`` values
    of the last axis by ``angles`` [S, d / 2]; ``x`` is [S, ..., d_I]."""
    half = angles.shape[-1]
    mid = (1,) * (x.ndim - 2)
    cos = jnp.cos(angles).reshape(angles.shape[0], *mid, half)
    sin = jnp.sin(angles).reshape(angles.shape[0], *mid, half)
    a, b = x[..., :half], x[..., half : 2 * half]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, x[..., 2 * half :]], axis=-1)


def _layer_norm(x, weight, bias, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight.astype(F32) + bias.astype(F32)


def _in_blocks(f, rows: int, *xs):
    """``f`` over blocks of ``rows`` rows of each ``x`` (first axis, a
    multiple of ``rows``), one block after another."""
    n = xs[0].shape[0] // rows
    out = jax.lax.map(f, tuple(x.reshape(n, rows, *x.shape[1:]) for x in xs))
    return jax.tree.map(lambda a: a.reshape(n * rows, *a.shape[2:]), out)


def forward(weights: dict, tokens, c: dict, quant=None, inner: bool = False, variant=None, logits_at=None):
    """tokens [S] -> logits [S, vocab] float32 (``logits_at``: at those
    positions alone), the whole sequence at once with no cache; with
    ``inner``, also ``{"picks": the chosen experts [expert layers, S, k],
    "latents": the rows [c^; R_t k_r] a latent cache would hold [layers, S,
    rank + d_r], "index_keys": the rotated index keys [layers, S, d_I],
    "selected": S_t as positions [layers, S, index_topk] int32, best first,
    -1 behind a row's last (``dense``: nothing)}``."""
    q_ = quantizer(quant)
    H, dn, dr, dv, R = (c["num_attention_heads"], c["qk_nope_head_dim"],
                        c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"])
    J, Di, topk = c["index_n_heads"], c["index_head_dim"], c["index_topk"]
    eps = c["rms_norm_eps"]
    norm_eps = c["index_norm_eps"]
    S = tokens.shape[0]
    Qb = min(QUERIES_AT_ONCE, S)
    Sp = -(-S // Qb) * Qb  # whole blocks of queries; what lies behind S is seen by no real position
    Fb = next(r for r in (ROWS_AT_ONCE, Qb) if Sp % r == 0)
    tokens = jnp.pad(tokens, (0, Sp - S))
    freqs, mscale, scale = frequencies(c)
    position = jnp.arange(Sp)
    angles = position.astype(F32)[:, None] * freqs[None, :]
    hg = min(HEADS_AT_ONCE, H)
    assert H % hg == 0
    k_sel = min(topk, Sp)

    def mm(a, w):
        return q_(a) @ q_(w.astype(F32))

    def by_head_group(w, width):  # [in, H x width] -> [H / hg, in, hg x width]
        return jnp.moveaxis(w.reshape(w.shape[0], H // hg, hg * width), 1, 0)

    def select(h, c_q, k_i, p):
        """S_t of every position, [Sp, k_sel] int32 (-1: none)."""

        def block(a):
            h_b, cq_b, pos_b = a
            q_i = rotate_halves(mm(cq_b, p["wi_q"]).reshape(Qb, J, Di), pos_b.astype(F32)[:, None] * freqs)
            w = mm(h_b, p["wi_w"]) * (J**-0.5 * Di**-0.5)
            prod = jnp.einsum("tjd,sd->tjs", q_(q_i), q_(k_i))
            score = jnp.sum(w[:, :, None] * jax.nn.relu(prod), axis=1)
            score = jnp.where(position[None, :] <= pos_b[:, None], score, -jnp.inf)
            vals, idx = jax.lax.top_k(score, k_sel)
            return jnp.where(vals > -jnp.inf, idx, -1).astype(jnp.int32)

        return _in_blocks(block, Qb, h, c_q, position)

    def attention(h, p):
        c_q = _rms_norm(mm(h, p["wq_a"]), p["q_norm"], eps)
        ckv = mm(h, p["wkva"])
        c_hat = _rms_norm(ckv[..., :R], p["kv_norm"], eps)
        k_r = rotate(ckv[None, :, R:], angles, mscale)[0]
        k_i = rotate_halves(_layer_norm(mm(h, p["wi_k"]), p["wi_knorm"], p["wi_kbias"], norm_eps), angles)
        latents.append(jnp.concatenate([c_hat, k_r], axis=-1))
        index_keys.append(k_i)
        causal = lambda pos_b: position[None, :] <= pos_b[:, None]  # noqa: E731
        if variant == "dense":
            kept = causal
        elif variant == "recent":
            kept = lambda pos_b: causal(pos_b) & (position[None, :] > pos_b[:, None] - topk)  # noqa: E731
        else:
            assert variant is None, variant
            chosen = select(h, c_q, k_i, p)
            selected.append(chosen)

        def heads(a):  # a group of heads: queries, keys and values made here, [Sp, hg, d] each
            wq, wkv = a
            q = mm(c_q, wq).reshape(Sp, hg, dn + dr)
            q = jnp.concatenate([q[..., :dn], rotate(q[None, ..., dn:], angles, mscale)[0]], axis=-1)
            kv = mm(c_hat, wkv).reshape(Sp, hg, dn + dv)
            k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r[:, None], (Sp, hg, dr))], axis=-1)

            def block(b):
                q_b, pos_b, *idx_b = b
                s = jnp.einsum("qhd,khd->hqk", q_(q_b), q_(k)) * scale
                if idx_b:  # the positions of S_t, as a mask
                    at = jnp.where(idx_b[0] < 0, Sp, idx_b[0])  # (-1 would name the last column)
                    keep = jnp.zeros((Qb, Sp), bool).at[jnp.arange(Qb)[:, None], at].set(True, mode="drop")
                else:
                    keep = kept(pos_b)
                s = jnp.where(keep[None], s, -jnp.inf)
                return jnp.einsum("hqk,khd->qhd", q_(jax.nn.softmax(s, axis=-1)), q_(kv[..., dn:]))

            return _in_blocks(block, Qb, q, position, *([chosen] if variant is None else []))

        a = jax.lax.map(heads, (by_head_group(p["wq_b"], dn + dr), by_head_group(p["wkvb"], dn + dv)))
        return mm(jnp.moveaxis(a, 0, 1).reshape(Sp, H * dv), p["wo"])  # [H / hg, Sp, hg, dv] -> [Sp, H dv]

    def dense(h, p):
        return _in_blocks(
            lambda a: mm(jax.nn.silu(mm(a[0], p["w_gate"])) * mm(a[0], p["w_up"]), p["w_down"]), Fb, h
        )

    picks, latents, index_keys, selected = [], [], [], []
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][tokens].astype(F32)
        for i, p in enumerate(weights["layers"], start=1):
            x = x + attention(_rms_norm(x, p["attn_norm"], eps), p)
            h = _rms_norm(x, p["mlp_norm"], eps)
            if i > c["first_k_dense_replace"]:
                y, idx = moe(h, p, c, mm)
                picks.append(idx)
            else:
                y = dense(h, p)
            x = x + y
        at = jnp.arange(S) if logits_at is None else jnp.asarray(logits_at)
        logits = mm(_rms_norm(x[at], weights["final_norm"], eps), weights["lm_head"])
    if not inner:
        return logits
    out = {
        "picks": jnp.stack(picks)[:, :S], "latents": jnp.stack(latents)[:, :S],
        "index_keys": jnp.stack(index_keys)[:, :S],
    }
    if selected:
        out["selected"] = jnp.stack(selected)[:, :S]
    return logits, out
