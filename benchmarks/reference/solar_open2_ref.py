"""Plain reference of the Solar Open 2 decoder (upstage Solar-Open2-250B,
``model_type: solar_open2``).

Published description (the model's ``config.json``; the KDA layer as Kimi
Linear, arXiv:2510.26692, and its modelling code give it): token embedding; per
layer ``x += Mixer(RMSNorm(x))``, ``x += MoE(RMSNorm(x))``; final RMSNorm;
untied head. Layers are numbered from 0. The mixer is grouped-query attention
at ``gqa_layers`` and KDA elsewhere; every layer has routed experts
(``first_k_dense_replace`` 0).

- **KDA** (``linear_attn_config``: H heads of d_k = d_v = ``head_dim``, key and
  value heads as many): ``[q~, k~, v~] = W_qkv a``, each channel through a
  causal depthwise convolution of ``short_conv_kernel_size`` taps, no bias,
  then SiLU; per head ``q = l2(q~) d^-1/2``, ``k = l2(k~)``, ``v = v~``; log
  decay per head and key channel ``g = -exp(A_log) softplus(W_f_up W_f_down a +
  dt_bias)``, ``alpha = exp(g)``; ``beta = 2 sigmoid(W_beta a)``
  (``kda_allow_neg_eigval``: in (0, 2)); state ``S`` [d, d] float32, zero at
  the start: ``S' = Diag(alpha_t) S_{t-1}``, ``S_t = S' + beta_t k_t (v_t - S'^T
  k_t)^T``, ``o_t = S_t^T q_t``; output ``W_o (RMSNorm_head(o_t) sigmoid(W_g_up
  W_g_down a))``. Token by token (``lax.scan`` over the sequence): no chunks,
  no triangular solve.
- **GQA** (``num_attention_heads`` query heads over ``num_key_value_heads``
  key/value heads of ``head_dim``): ``q = W_q a``, ``k = W_k a``, ``v = W_v a``,
  ``gate = W_g a``; **no rotation and no other position signal** (``use_rope``
  false); scores ``q.k head_dim^-1/2``, causal, softmax; ``W_o (o
  sigmoid(gate))`` (``use_gqa_gate``). One masked softmax over all keys, a run
  of queries at a time so that it fits.
- **Experts**: ``s = sigmoid(W_r m)`` over all routed experts of the model, in
  float32; the ``num_experts_per_tok`` largest of ``s + b`` chosen (``b`` the
  selection bias); weights ``s_i / sum of the chosen s`` (``norm_topk_prob``)
  times ``routed_scaling_factor``; each chosen expert a SwiGLU of
  ``moe_intermediate_size``, plus one shared expert of the same width. A
  masked loop over the experts *held here* (``n_routed_experts`` of them from
  ``expert_offset``; ``published.n_routed_experts`` is the router's width);
  what the absent ones would add is left out, as in the program.

No cache, no kernel, no batching: one sequence at once. This file draws no
weights: the output check hands ``forward`` the ones the served program drew
from the seed (one dict a layer, as ``ray_tpu.models.solar_open2`` names them).
Departures are under ``assumed`` in the configuration's file.

``wrong`` names one departure from the mathematics above, for the output
check's controls: ``beta_unit`` (``beta = sigmoid``: the negative eigenvalues
dropped), ``ungated`` (no ``sigmoid(gate)`` in the GQA layer), ``rotated`` (the
GQA layer's ``q`` and ``k`` rotated by position at ``rope_theta``), and, at
position ``cut_at`` (where a served prompt's second chunk begins),
``stale_state`` (the KDA state is zero there) and ``lost_tail`` (the
convolution sees zeros before it).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.common import F32, quantizer

WRONGS = ("beta_unit", "ungated", "rotated", "stale_state", "lost_tail")
ROWS = 1024  # token-wise parts, rows at a time
QUERIES = 128  # attention, queries at a time against every key
HEADS = 16  # the delta rule, heads at a time


def layer_kinds(c: dict) -> list:
    """``"gqa"`` or ``"kda"`` of layers 0..num_hidden_layers - 1."""
    gqa = set(c["gqa_layers"])
    return ["gqa" if i in gqa else "kda" for i in range(c["num_hidden_layers"])]


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


def kda_recurrence(q, k, v, g, beta, S0, stops=(), zero_at=None):
    """Token by token. ``q, k, g`` [S, H, d_k], ``v`` [S, H, d_v], ``beta`` [S,
    H], ``S0`` [H, d_k, d_v] -> ``(o [S, H, d_v], the state after as many
    tokens as each of ``stops`` says)``. ``zero_at``: the state is put to zero
    before that token (a control)."""

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = jnp.exp(g_t)[..., None] * S  # Diag(alpha_t) S
        u = b_t[..., None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    n = q.shape[0]
    marks = sorted({0, n, *stops, *(() if zero_at is None else (zero_at,))})
    assert marks[0] == 0 and marks[-1] == n, (marks, n)
    S, outs, kept = S0, [], {}
    for lo, hi in zip(marks, marks[1:]):
        if lo == zero_at:
            S = jnp.zeros_like(S)
        S, o = jax.lax.scan(step, S, tuple(a[lo:hi] for a in (q, k, v, g, beta)))
        outs.append(o)
        kept[hi] = S
    kept[0] = S0
    return jnp.concatenate(outs), [kept[s] for s in stops]


def kda(a, p, c: dict, mm, wrong=None, cut_at=None, state_at=()):
    """``a`` [S, D] normed -> ``(out [S, D], the state [stops, H, d, d] after
    each of ``state_at`` tokens, the convolution's last K - 1 input rows
    [stops, K - 1, 3 H d] there)``. The heads go ``HEADS`` at a time, one run
    after the other: a head's mathematics reads nothing of another's, and 64
    heads of a 14k-token sequence at once are 10 GB of float32."""
    la = c["linear_attn_config"]
    H, d, K = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    assert la["num_kv_heads"] in (None, H)
    S, D, eps = a.shape[0], a.shape[1], c["rms_norm_eps"]
    Hg = math.gcd(H, HEADS)
    G, t = H // Hg, jnp.arange(S)

    def runs(w, parts):  # [..., parts H d] -> [G, ..., parts Hg d]: each run's columns, q | k | v within it
        w = w.reshape(*w.shape[:-1], parts, G, Hg * d)
        return jnp.moveaxis(w, -2, 0).reshape(G, *w.shape[:-3], parts * Hg * d)

    f_low, g_low = _in_runs(lambda rows: (mm(rows, p["f_down"]), mm(rows, p["g_down"])), a, ROWS)
    l2 = lambda m: m * jax.lax.rsqrt(jnp.sum(m * m, axis=-1, keepdims=True) + 1e-6)  # noqa: E731

    def heads(w):
        wqkv, conv, f_up, dt_bias, A_log, wb, g_up = w
        x = _in_runs(lambda rows: mm(rows, wqkv), a, ROWS)  # [S, 3 Hg d], before the convolution
        xp = jnp.pad(x, ((K - 1, 0), (0, 0)))  # zeros before the start
        mixed = 0.0
        for j in range(K):  # tap j of position t reads row t - (K - 1) + j
            rows = xp[j : j + S]
            if wrong == "lost_tail":
                rows = jnp.where(((t >= cut_at) & (t - (K - 1) + j < cut_at))[:, None], 0.0, rows)
            mixed = mixed + conv[j].astype(F32) * rows
        q, k, v = (m.reshape(S, Hg, d) for m in jnp.split(jax.nn.silu(mixed), 3, axis=-1))
        f = _in_runs(lambda rows: mm(rows, f_up), f_low, ROWS) + dt_bias
        g = -jnp.exp(A_log)[:, None] * jax.nn.softplus(f.reshape(S, Hg, d))
        beta = jax.nn.sigmoid(_in_runs(lambda rows: mm(rows, wb), a, ROWS))
        if c["kda_allow_neg_eigval"] and wrong != "beta_unit":
            beta = 2.0 * beta
        o, states = kda_recurrence(
            l2(q) * d**-0.5, l2(k), v, g, beta, jnp.zeros((Hg, d, d), F32),
            stops=tuple(state_at), zero_at=cut_at if wrong == "stale_state" else None,
        )
        gate = jax.nn.sigmoid(_in_runs(lambda rows: mm(rows, g_up), g_low, ROWS))
        o = _rms_norm(o, p["o_norm"], eps).reshape(S, Hg * d) * gate
        tails = [xp[s : s + K - 1] for s in state_at]  # rows s - (K - 1) .. s - 1
        return o, jnp.stack(states) if states else (), jnp.stack(tails) if tails else ()

    o, states, tails = jax.lax.map(heads, (
        runs(p["wqkv"], 3), runs(p["conv"], 3), runs(p["f_up"], 1), runs(p["dt_bias"], 1),
        p["A_log"].reshape(G, Hg), jnp.moveaxis(p["wb"].reshape(D, G, Hg), 1, 0), runs(p["g_up"], 1),
    ))
    o = jnp.moveaxis(o, 0, 1).reshape(S, H * d)
    if state_at:  # [G, stops, Hg, d, d] and [G, stops, K - 1, 3 Hg d], back to the heads' and the columns' order
        n = len(state_at)
        states = jnp.moveaxis(states, 0, 1).reshape(n, H, d, d)
        tails = jnp.moveaxis(tails.reshape(G, n, K - 1, 3, Hg * d), 0, 3).reshape(n, K - 1, 3 * H * d)
    return _in_runs(lambda rows: mm(rows, p["wo"]), o, ROWS), states, tails


def _rotate(t, positions, theta):
    """``t`` [S, heads, Dh] rotated in halves by ``positions`` [S]."""
    half = t.shape[-1] // 2
    angles = positions.astype(F32)[:, None] * theta ** (-jnp.arange(half, dtype=F32) / half)
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], axis=-1)


def gqa(a, p, c: dict, mm, q_, wrong=None):
    """``a`` [S, D] normed -> ``(out [S, D], each position's ``[k; v]`` [S, 2 KH
    Dh])``; ``q_`` rounds the operands of the two products that are not with
    a weight."""
    H, KH, Dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    assert not c["use_rope"] and c["use_gqa_gate"]
    S = a.shape[0]
    pos = jnp.arange(S)

    def project(rows):
        a, pos = rows
        q, k = mm(a, p["wq"]).reshape(-1, H, Dh), mm(a, p["wk"]).reshape(-1, KH, Dh)
        if wrong == "rotated":
            q, k = _rotate(q, pos, c["rope_theta"]), _rotate(k, pos, c["rope_theta"])
        return q, k, mm(a, p["wv"]).reshape(-1, KH, Dh), mm(a, p["wg"])

    q, k, v, g = _in_runs(project, (a, pos), ROWS)
    kq, vq = q_(k), q_(v)

    def attend(rows):
        q, i = rows  # [n, H, Dh], [n]
        s = jnp.einsum("qkgd,skd->kgqs", q_(q).reshape(-1, KH, H // KH, Dh), kq) * Dh**-0.5
        s = jnp.where((pos[None, :] <= i[:, None])[None, None], s, -jnp.inf)
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
    if c["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * c["routed_scaling_factor"]


def swiglu(m, gate, up, down, mm):
    return mm(jax.nn.silu(mm(m, gate)) * mm(m, up), down)


def experts(m, p, c: dict, mm, shared: bool = True):
    """The experts held here on their picks, plus the shared expert (left out
    with ``shared`` false: what every chip computes alike is counted once when
    the shares of a layer are added up); also the picks."""
    idx, w = route(m, p, c, mm)

    def one(y, e):
        n, gate, up, down = e
        w_e = jnp.sum(jnp.where(idx == n + c["expert_offset"], w, 0.0), axis=-1)
        return y + w_e[..., None] * swiglu(m, gate, up, down, mm), None

    held = jnp.arange(p["e_up"].shape[0])
    y, _ = jax.lax.scan(one, jnp.zeros_like(m), (held, p["e_gate"], p["e_up"], p["e_down"]))
    if shared:
        y = y + swiglu(m, p["s_gate"], p["s_up"], p["s_down"], mm)
    return y, idx


def forward(weights: dict, tokens, c: dict, quant=None, inner: bool = False, wrong=None,
            cut_at=None, logits_at=None, kv_rows=None, state_at=()):
    """tokens [S] -> logits [S, vocab] float32 (at the positions ``logits_at``
    alone where given), one sequence at once with no cache; with ``inner``,
    also ``{"picks": the chosen experts [layers, S, k], "kv": each position's
    keys and values ``[k; v]`` [GQA layers, S, 2 KH Dh] (of the positions
    ``kv_rows``, a slice, alone where given), "state": [stops, KDA layers, H, d,
    d] and "conv": [stops, KDA layers, K - 1, 3 H d], the recurrent state and
    the convolution's last input rows after as many tokens as each of
    ``state_at`` says}``."""
    assert wrong in (None, *WRONGS), wrong
    assert (cut_at is not None) == (wrong in ("stale_state", "lost_tail")), (wrong, cut_at)
    assert c["first_k_dense_replace"] == 0 and c["n_shared_experts"] == 1
    q_ = quantizer(quant)
    eps = c["rms_norm_eps"]

    def mm(a, w):
        return q_(a) @ q_(w.astype(F32))

    chosen, kvs, states, tails = [], [], [], []
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][tokens].astype(F32)
        for kind, p in zip(layer_kinds(c), weights["layers"]):
            a = _in_runs(lambda rows, p=p: _rms_norm(rows, p["attn_norm"], eps), x, ROWS)
            if kind == "gqa":
                y, kv = gqa(a, p, c, mm, q_, wrong)
                kvs.append(kv if kv_rows is None else kv[kv_rows])
            else:
                y, S_at, tail_at = kda(a, p, c, mm, wrong, cut_at, state_at)
                states.append(S_at)
                tails.append(tail_at)

            def rest(rows, p=p):
                x, y = rows
                x = x + y
                f, idx = experts(_rms_norm(x, p["mlp_norm"], eps), p, c, mm)
                return x + f, idx

            x, idx = _in_runs(rest, (x, y), ROWS)
            chosen.append(idx)
        last = x if logits_at is None else x[jnp.asarray(logits_at)]
        logits = mm(_rms_norm(last, weights["final_norm"], eps), weights["lm_head"])
    if inner:
        out = {"picks": jnp.stack(chosen), "kv": jnp.stack(kvs)}
        if state_at:  # a layer's are [stops, ...]
            out["state"], out["conv"] = jnp.stack(states, axis=1), jnp.stack(tails, axis=1)
        return logits, out
    return logits
