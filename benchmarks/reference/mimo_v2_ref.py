"""Plain reference of the MiMo-V2 decoder (XiaomiMiMo MiMo-V2.5's language
model, ``model_type: mimo_v2``).

Published description (the model's ``config.json`` and its card): token
embedding; each layer ``x += Attention(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``
(``layernorm_epsilon``); final RMSNorm; untied head; no biases.

- **Attention**, of two kinds by ``hybrid_layer_pattern`` (0 full, 1 window),
  ``num_attention_heads`` query heads over ``num_key_value_heads`` (full) or
  ``swa_num_key_value_heads`` (window) key/value heads; queries and keys of
  ``head_dim`` (192), values of ``v_head_dim`` (128): ``q = W_q a``, ``k = W_k
  a``, ``v = attention_value_scale W_v a``; the first ``int(partial_rotary_factor
  head_dim)`` = 64 lanes of each ``q`` and ``k`` head rotated by position, in
  halves (lane ``i`` with ``i + 32``), with base ``rope_theta`` in a full layer
  and ``swa_rope_theta`` in a window layer; scores ``q.k head_dim^-1/2``,
  causal; a window layer's query ``i`` sees key ``j`` only where ``0 <= i - j <
  sliding_window``, and (``add_swa_attention_sink_bias``) its softmax has one
  more term a query head, the learned ``sink_h``: ``p_ij = exp(s_ij) / (sum_j'
  exp(s_ij') + exp(sink_h))``, which adds no value. ``W_o`` over ``num_attention_heads
  x v_head_dim``.
- **Feed-forward**: a SwiGLU of ``intermediate_size`` where ``moe_layer_freq``
  says 0; elsewhere ``s = sigmoid(W_r m)`` over all routed experts of the model
  in float32, the ``num_experts_per_tok`` largest of ``s + b`` chosen (``b`` the
  selection bias of ``noaux_tc``), weights ``s_i / sum of the chosen s``
  (``norm_topk_prob``) times ``routed_scaling_factor`` (null: 1), each chosen
  expert a SwiGLU of ``moe_intermediate_size``; no shared expert. A masked loop
  over the experts *held here* (``n_routed_experts`` of them from
  ``expert_offset``; ``published.n_routed_experts`` is the router's width); what
  the absent ones would add is left out, as in the program.

No cache, no kernel, no batching: the whole sequence at once, token-wise parts
a run of rows at a time and attention a run of queries against every key at a
time. This file draws no weights: the output check hands ``forward`` the ones
the served program drew from the seed (one dict a layer, as
``ray_tpu.models.mimo_v2`` names them). Departures are under ``assumed`` in the
configuration's file.

``wrong`` names one departure from the mathematics above, for the output
check's controls: ``no_sink`` (a window layer's softmax over its keys alone),
``unscaled_values`` (no ``attention_value_scale``), ``rope_everywhere`` (all
192 lanes of a head rotated), ``one_theta`` (window layers rotated with the
full layers' base), ``no_window`` (window layers attend everything).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.common import F32, quantizer

WRONGS = ("no_sink", "unscaled_values", "rope_everywhere", "one_theta", "no_window")
ROWS = 1024  # token-wise parts, rows at a time
QUERIES = 128  # attention, queries at a time against every key


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _in_runs(f, xs, run: int):
    """``f`` over runs of ``run`` rows of ``xs`` (an array [S, ...] or a tuple
    of them); ``f`` maps a run to a pytree of arrays with the rows leading."""
    S = jax.tree.leaves(xs)[0].shape[0]
    pad = -S % run
    cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(-1, run, *a.shape[1:])  # noqa: E731
    out = jax.lax.map(f, jax.tree.map(cut, xs))
    return jax.tree.map(lambda y: y.reshape(-1, *y.shape[2:])[:S], out)


def _rotate(t, positions, theta, lanes: int):
    """``t`` [S, heads, Dk]: its first ``lanes`` lanes rotated in halves by
    ``positions`` [S], the others as they are."""
    half = lanes // 2
    angles = positions.astype(F32)[:, None] * theta ** (-jnp.arange(half, dtype=F32) / half)
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    t1, t2 = t[..., :half], t[..., half:lanes]
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos, t[..., lanes:]], axis=-1)


def attention(a, p, c: dict, mm, q_, window_layer: bool, wrong=None):
    """``a`` [S, D] normed -> ``(out [S, D], each position's ``[k; v]`` [S, KH
    (Dk + Dv)], the mean probability the sink takes of the rows that see a
    whole window, or None)``; ``q_`` rounds the operands of the two products
    that are not with a weight."""
    H, Dk, Dv = c["num_attention_heads"], c["head_dim"], c["v_head_dim"]
    KH = c["swa_num_key_value_heads"] if window_layer else c["num_key_value_heads"]
    assert (c["swa_head_dim"], c["swa_v_head_dim"]) == (Dk, Dv)
    S = a.shape[0]
    pos = jnp.arange(S)
    lanes = Dk if wrong == "rope_everywhere" else int(c["partial_rotary_factor"] * Dk)
    theta = c["swa_rope_theta"] if window_layer and wrong != "one_theta" else c["rope_theta"]
    value_scale = 1.0 if wrong == "unscaled_values" else c["attention_value_scale"]

    def project(rows):
        a, pos = rows
        q = _rotate(mm(a, p["wq"]).reshape(-1, H, Dk), pos, float(theta), lanes)
        k = _rotate(mm(a, p["wk"]).reshape(-1, KH, Dk), pos, float(theta), lanes)
        return q, k, value_scale * mm(a, p["wv"]).reshape(-1, KH, Dv)

    q, k, v = _in_runs(project, (a, pos), ROWS)
    window = c["sliding_window"] if window_layer and wrong != "no_window" else None
    has_sink = c["add_swa_attention_sink_bias"] if window_layer else c["add_full_attention_sink_bias"]
    sink = p["sink"].astype(F32).reshape(KH, H // KH) if has_sink and wrong != "no_sink" else None
    kq, vq = q_(k), q_(v)

    def attend(rows):
        q, i = rows  # [n, H, Dk], [n]
        s = jnp.einsum("qkgd,skd->kgqs", q_(q).reshape(-1, KH, H // KH, Dk), kq) * Dk**-0.5
        seen = pos[None, :] <= i[:, None]
        if window is not None:
            seen &= i[:, None] - pos[None, :] < window
        s = jnp.where(seen[None, None], s, -jnp.inf)
        if sink is None:
            pa, taken = jax.nn.softmax(s, axis=-1), jnp.zeros(q.shape[0], F32)
        else:  # one more term in the sum, which carries no value
            column = jnp.broadcast_to(sink[:, :, None, None], (*s.shape[:3], 1))
            pa = jax.nn.softmax(jnp.concatenate([s, column], axis=-1), axis=-1)
            pa, taken = pa[..., :-1], jnp.mean(pa[..., -1], axis=(0, 1))
        return jnp.einsum("kgqs,skd->qkgd", q_(pa), vq).reshape(-1, H * Dv), taken

    o, taken = _in_runs(attend, (q, pos), QUERIES)
    share = None
    if sink is not None and window is not None:
        whole = pos >= window - 1
        share = jnp.sum(jnp.where(whole, taken, 0.0)) / jnp.maximum(jnp.sum(whole), 1)
    out = _in_runs(lambda rows: mm(rows, p["wo"]), o, ROWS)
    return out, jnp.concatenate([k.reshape(S, -1), v.reshape(S, -1)], axis=-1), share


def route(m, p, c: dict, mm):
    """Chosen experts [..., k] and their weights, over all routed experts."""
    assert c["scoring_func"] == "sigmoid" and c["topk_method"] == "noaux_tc"
    assert c["n_group"] == c["topk_group"] == 1
    s = jax.nn.sigmoid(mm(m, p["router"]))
    _, idx = jax.lax.top_k(s + p["router_bias"], c["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if c["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * (c["routed_scaling_factor"] or 1.0)


def swiglu(m, gate, up, down, mm):
    return mm(jax.nn.silu(mm(m, gate)) * mm(m, up), down)


def experts(m, p, c: dict, mm):
    """The experts held here on their picks (no shared expert); also the picks."""
    assert not c["n_shared_experts"]
    idx, w = route(m, p, c, mm)

    def one(y, e):
        n, gate, up, down = e
        w_e = jnp.sum(jnp.where(idx == n + c["expert_offset"], w, 0.0), axis=-1)
        return y + w_e[..., None] * swiglu(m, gate, up, down, mm), None

    held = jnp.arange(p["e_up"].shape[0])
    y, _ = jax.lax.scan(one, jnp.zeros_like(m), (held, p["e_gate"], p["e_up"], p["e_down"]))
    return y, idx


def forward(weights: dict, tokens, c: dict, quant=None, inner: bool = False, wrong=None,
            logits_at=None, kv_rows=None):
    """tokens [S] -> logits [S, vocab] float32 (at the positions ``logits_at``
    alone where given), one sequence at once with no cache; with ``inner``,
    also ``{"picks": the chosen experts [expert layers, S, k], "kv": each
    position's keys and values ``[k; v]`` {"full": [full layers, S, KH (Dk +
    Dv)], "window": [window layers, S, KH' (Dk + Dv)]}`` (of the positions
    ``kv_rows``, a slice, alone where given), "sink_share": the mean
    probability the sinks take of the rows that see a whole window, a window
    layer each}``."""
    assert wrong in (None, *WRONGS), wrong
    q_ = quantizer(quant)
    eps = c["layernorm_epsilon"]

    def mm(a, w):
        return q_(a) @ q_(w.astype(F32))

    chosen, kvs, shares = [], {"full": [], "window": []}, []
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][tokens].astype(F32)
        for kind, moe, p in zip(c["hybrid_layer_pattern"], c["moe_layer_freq"], weights["layers"]):
            a = _in_runs(lambda rows, p=p: _rms_norm(rows, p["in_norm"], eps), x, ROWS)
            y, kv, share = attention(a, p, c, mm, q_, bool(kind), wrong)
            kvs["window" if kind else "full"].append(kv if kv_rows is None else kv[kv_rows])
            if share is not None:
                shares.append(share)

            def rest(rows, p=p, moe=moe):
                x, y = rows
                x = x + y
                m = _rms_norm(x, p["mlp_norm"], eps)
                if moe:
                    f, idx = experts(m, p, c, mm)
                else:
                    f, idx = swiglu(m, p["w_gate"], p["w_up"], p["w_down"], mm), None
                return x + f, idx

            x, idx = _in_runs(rest, (x, y), ROWS)
            if idx is not None:
                chosen.append(idx)
        last = x if logits_at is None else x[jnp.asarray(logits_at)]
        logits = mm(_rms_norm(last, weights["final_norm"], eps), weights["lm_head"])
    if inner:
        return logits, {
            "picks": jnp.stack(chosen),
            "kv": {part: jnp.stack(rows) for part, rows in kvs.items() if rows},
            "sink_share": jnp.stack(shares) if shares else jnp.zeros((0,), F32),
        }
    return logits
