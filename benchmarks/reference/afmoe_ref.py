"""Plain reference of the AFMoE decoder (arcee-ai Trinity-Large-Preview,
``model_type: afmoe``).

Published description (the model's ``config.json``, its card and modelling
code): token embedding times ``sqrt(hidden_size)`` (``mup_enabled``); each
layer ``x += RMSNorm(Attention(RMSNorm(x)))``, ``x += RMSNorm(FFN(RMSNorm(x)))``
(four norms: the sandwich); final RMSNorm; untied head.

- **Attention** (``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``head_dim``): ``q = W_q a``, ``k =
  W_k a``, ``v = W_v a``, ``g = W_g a``; ``q`` and ``k`` each through an RMSNorm
  over a head; a layer whose ``layer_types`` entry is ``sliding_attention``
  rotates ``q`` and ``k`` by position (the whole head in halves, ``rope_theta``,
  no scaling) and sees key ``j`` from query ``i`` only where ``0 <= i - j <
  sliding_window``; a ``full_attention`` layer is causal and **does not rotate
  at all**. Scores ``q.k head_dim^-1/2``, softmax, ``W_o (o sigmoid(g))``.
- **Feed-forward**: the leading ``num_dense_layers`` layers a SwiGLU of
  ``intermediate_size``; the others ``s = sigmoid(W_r m)`` over all routed
  experts of the model in float32, the ``num_experts_per_tok`` largest of ``s +
  b`` chosen (``b`` the selection bias), weights ``s_i / sum of the chosen s``
  (``route_norm``) times ``route_scale``, each chosen expert a SwiGLU of
  ``moe_intermediate_size``, plus one shared expert of the same width. A
  masked loop over the experts *held here* (``num_experts`` of them from
  ``expert_offset``; ``published.num_experts`` is the router's width); what the
  absent ones would add is left out, as in the program.

No cache, no kernel, no batching: the whole sequence at once, token-wise parts
a run of rows at a time and attention a run of queries against every key at a
time, so that a context of 15k positions fits beside the program it is
compared with. This file draws no weights: the output check hands ``forward``
the ones the served program drew from the seed (one dict a layer, as
``ray_tpu.models.afmoe`` names them). Departures are under ``assumed`` in the
configuration's file.

``wrong`` names one departure from the mathematics above, for the output
check's controls: ``no_window`` (sliding layers attend everything),
``rope_everywhere`` (full layers rotated too), ``ungated`` (no ``sigmoid(g)``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.common import F32, quantizer

WRONGS = ("no_window", "rope_everywhere", "ungated")
SLIDING = "sliding_attention"
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


def _rotate(t, positions, theta):
    """``t`` [S, heads, Dh] rotated in halves by ``positions`` [S]."""
    half = t.shape[-1] // 2
    angles = positions.astype(F32)[:, None] * theta ** (-jnp.arange(half, dtype=F32) / half)
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], axis=-1)


def attention(a, p, c: dict, mm, q_, sliding: bool, wrong=None):
    """``a`` [S, D] normed -> ``(out [S, D], each position's ``[k; v]`` [S, 2 KH
    Dh])``; ``q_`` rounds the operands of the two products that are not with
    a weight."""
    H, KH, Dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    S, eps = a.shape[0], c["rms_norm_eps"]
    pos = jnp.arange(S)

    def project(rows):
        a, pos = rows
        q = _rms_norm(mm(a, p["wq"]).reshape(-1, H, Dh), p["q_norm"], eps)
        k = _rms_norm(mm(a, p["wk"]).reshape(-1, KH, Dh), p["k_norm"], eps)
        if sliding or wrong == "rope_everywhere":
            q, k = _rotate(q, pos, c["rope_theta"]), _rotate(k, pos, c["rope_theta"])
        return q, k, mm(a, p["wv"]).reshape(-1, KH, Dh), mm(a, p["wg"])

    q, k, v, g = _in_runs(project, (a, pos), ROWS)
    window = c["sliding_window"] if sliding and wrong != "no_window" else None
    kq, vq = q_(k), q_(v)

    def attend(rows):
        q, i = rows  # [n, H, Dh], [n]
        s = jnp.einsum("qkgd,skd->kgqs", q_(q).reshape(-1, KH, H // KH, Dh), kq) * Dh**-0.5
        seen = pos[None, :] <= i[:, None]
        if window is not None:
            seen &= i[:, None] - pos[None, :] < window
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", q_(jax.nn.softmax(s, axis=-1)), vq).reshape(-1, H * Dh)

    o = _in_runs(attend, (q, pos), QUERIES)
    if wrong != "ungated":
        o = o * jax.nn.sigmoid(g)
    out = _in_runs(lambda rows: mm(rows, p["wo"]), o, ROWS)
    return out, jnp.concatenate([k.reshape(S, -1), v.reshape(S, -1)], axis=-1)


def route(m, p, c: dict, mm):
    """Chosen experts [..., k] and their weights, over all routed experts."""
    s = jax.nn.sigmoid(mm(m, p["router"]))
    _, idx = jax.lax.top_k(s + p["router_bias"], c["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if c["route_norm"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * c["route_scale"]


def swiglu(m, gate, up, down, mm):
    return mm(jax.nn.silu(mm(m, gate)) * mm(m, up), down)


def experts(m, p, c: dict, mm):
    """The experts held here on their picks, plus the shared expert; also the
    picks."""
    idx, w = route(m, p, c, mm)

    def one(y, e):
        n, gate, up, down = e
        w_e = jnp.sum(jnp.where(idx == n + c["expert_offset"], w, 0.0), axis=-1)
        return y + w_e[..., None] * swiglu(m, gate, up, down, mm), None

    held = jnp.arange(p["e_up"].shape[0])
    y, _ = jax.lax.scan(one, jnp.zeros_like(m), (held, p["e_gate"], p["e_up"], p["e_down"]))
    return y + swiglu(m, p["s_gate"], p["s_up"], p["s_down"], mm), idx


def forward(weights: dict, tokens, c: dict, quant=None, inner: bool = False, wrong=None,
            logits_at=None, kv_rows=None):
    """tokens [S] -> logits [S, vocab] float32 (at the positions ``logits_at``
    alone where given), one sequence at once with no cache; with ``inner``,
    also ``{"picks": the chosen experts [expert layers, S, k], "kv": each
    position's keys and values ``[k; v]`` {"full": [full layers, S, 2 KH Dh],
    "window": [sliding layers, S, 2 KH Dh]}}`` (of the positions ``kv_rows``, a
    slice, alone where given)."""
    assert wrong in (None, *WRONGS), wrong
    q_ = quantizer(quant)
    eps = c["rms_norm_eps"]

    def mm(a, w):
        return q_(a) @ q_(w.astype(F32))

    chosen, kvs = [], {"full": [], "window": []}
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][tokens].astype(F32)
        if c["mup_enabled"]:
            x = x * c["hidden_size"] ** 0.5
        for n, (kind, p) in enumerate(zip(c["layer_types"], weights["layers"])):
            sliding = kind == SLIDING
            a = _in_runs(lambda rows, p=p: _rms_norm(rows, p["in_norm"], eps), x, ROWS)
            y, kv = attention(a, p, c, mm, q_, sliding, wrong)
            kvs["window" if sliding else "full"].append(kv if kv_rows is None else kv[kv_rows])

            def rest(rows, p=p, dense=n < c["num_dense_layers"]):
                x, y = rows
                x = x + _rms_norm(y, p["post_attn_norm"], eps)
                m = _rms_norm(x, p["pre_mlp_norm"], eps)
                if dense:
                    f, idx = swiglu(m, p["w_gate"], p["w_up"], p["w_down"], mm), None
                else:
                    f, idx = experts(m, p, c, mm)
                return x + _rms_norm(f, p["post_mlp_norm"], eps), idx

            x, idx = _in_runs(rest, (x, y), ROWS)
            if idx is not None:
                chosen.append(idx)
        last = x if logits_at is None else x[jnp.asarray(logits_at)]
        logits = mm(_rms_norm(last, weights["final_norm"], eps), weights["lm_head"])
    if inner:
        return logits, {
            "picks": jnp.stack(chosen),
            "kv": {part: jnp.stack(rows) for part, rows in kvs.items() if rows},
        }
    return logits
