"""What the families with latent attention and routed experts share: one
implementation, which :mod:`ray_tpu.models.kimi_linear` and
:mod:`ray_tpu.models.mla_moe` both import.

- **The latent row.** One ``[RMSNorm(c); k_r]`` row of ``kv_lora_rank +
  qk_rope_head_dim`` values a position, for all heads, written into the block
  pool under the engine's block tables (:func:`mla_latent`). Where the family
  rotates, ``k_r`` is rotated by its position *before* it is written, so a row
  in the pool is good for whoever reads it later, a request that shares the
  block by prefix included.
- **Attention over latent rows**, in the two forms serving needs.
  :func:`mla_prefill` expands keys and values per head, a stretch of the table
  and a run of queries at a time with a running softmax, over the positions
  that run may see: no ``[H, T, table]`` score tensor exists. :func:`mla_decode` absorbs the
  expansion into the query and the output, so that a step reads latent rows as
  they lie in the pool.
- **What a family leaves off is an argument it does not give**: ``rope`` (the
  cosines and sines of the tokens' positions, :func:`rope_tables`; None: no
  rotation), ``scale`` (None: ``(d_nope + d_rope)^-1/2``), the query's low-rank
  pair (``wq_a`` / ``q_norm`` / ``wq_b`` in the layer's parameters; absent: one
  ``wq``), the router's selection bias (``router_bias``; absent: none), and the
  expert groups (``cfg.n_group`` 1: a plain top-k).
- **The expert layer** (:func:`route`, :func:`moe_ffn`): routing over all
  experts of the model, computing the part of the result that the experts held
  here give (``experts_held`` of them from ``expert_offset``). No capacity and
  no dropped token: the (token, pick) pairs that land here are sorted by expert
  and run through grouped matrix products (:mod:`ray_tpu.ops.moe_gmm`'s
  kernel on a TPU, ``jax.lax.ragged_dot`` elsewhere), a long prompt's in
  passes that stop where the landed pairs end. What absent experts would add is
  left out; on one chip the layer runs without its exchange. Here too a
  family says what it has by what its layer's parameters hold: a shared expert
  (``s_up``; absent: the routed sum alone), experts with a
  gate (``e_gate``, ``s_gate``: ``act(gate) * up``) or without one
  (``act(up)``), ``act`` the configuration's ``hidden_act``; and the routed
  part at the model's width, or in a latent (``latent_in`` / ``latent_out``:
  the router and the shared expert read the layer's input, the routed experts
  read ``latent_in`` of it, and their weighted sum goes back through
  ``latent_out``).

**The module's name** means latent *attention* and routed experts. NVIDIA's
"LatentMoE" (``nemotron_h``) is another thing, the expert layer itself computed
in a latent, which :func:`moe_ffn` now also does: that family has no latent
attention and imports the expert layer, :func:`balance_routers` and
:func:`span_fields` alone.

A configuration handed to these functions has the fields ``n_head``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``rms_eps``, ``dtype``, ``n_experts``, ``experts_held``, ``expert_offset``,
``experts_per_token``, ``n_group``, ``topk_group``, ``routed_scaling``,
``renormalize``, ``hidden_act``, ``n_moe_layers`` and ``is_moe(layer)`` (the
expert layer reads only those from ``n_experts`` on).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ray_tpu.models.common import _rms_norm, stage
from ray_tpu.models.llama import _mlp_sublayer
from ray_tpu.ops import moe_gmm

_F32 = jnp.float32

# Positions of the table that one step of the prefill's running softmax
# expands and scores, and queries that go through it together: [H, 512, 512]
# float32 is 67 MB at 64 heads, where the 2,048 bucket against the whole table
# of 4,096 would be 2.1 GB a layer.
KEY_POSITIONS = 512

# ``hidden_act`` -> the experts' activation. ``relu2`` is the squared ReLU.
ACTIVATIONS = {"silu": jax.nn.silu, "relu2": lambda x: jnp.square(jax.nn.relu(x))}

# Sorted (token, pick) rows that one pass of the grouped products takes: a
# decode step's rows whole, and a 2,048-token prompt's 16,384 in as many
# passes as hold a pick that landed here (one, at a sixteenth of the experts).
ROWS_A_PASS = 2048


# ---------------------------------------------------------------------------
# Rotation (decoupled RoPE on the shared key part, YaRN frequencies)


def yarn_mscale(factor: float, mscale: float) -> float:
    """``0.1 m ln s + 1`` (1 where nothing is stretched)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(
    dim: int, theta: float, factor: float = 1.0, original_max: int = 4096,
    beta_fast: float = 32.0, beta_slow: float = 1.0,
):
    """The ``dim / 2`` angular frequencies of a rotation over ``dim`` values:
    ``theta^(-2i/dim)``, and under YaRN (``factor`` > 1) those divided by
    ``factor`` where a pair turns fewer than ``beta_slow`` times over the
    original context, left alone where it turns more than ``beta_fast`` times,
    and a linear ramp between (the published correction range, floor and
    ceiling of ``dim ln(original_max / (beta 2 pi)) / (2 ln theta)``)."""
    i = jnp.arange(dim // 2, dtype=_F32)
    plain = theta ** (-2.0 * i / dim)
    if factor <= 1.0:
        return plain

    def turns_at(beta):
        return dim * math.log(original_max / (beta * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    keep = 1.0 - jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 - keep) * plain / factor + keep * plain


def rope_tables(freqs, positions, mscale: float = 1.0):
    """``(cos, sin)`` [..., dim / 2] float32 of ``positions`` [...] int."""
    angles = positions.astype(_F32)[..., None] * freqs
    return jnp.cos(angles) * mscale, jnp.sin(angles) * mscale


def rotate(x, cos, sin):
    """Rotate the pairs ``(2i, 2i + 1)`` of the last axis of ``x`` (the
    family's interleaved convention) by the angles of ``cos`` / ``sin``
    [..., dim / 2], which broadcast against ``x``'s leading axes."""
    pairs = x.astype(_F32).reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# Latent attention


def whole_tiles(width: int) -> int:
    """``width`` rounded up to whole 128-lane tiles. A pool whose rows are
    576 wide is kept by a TPU with the *block* axis minor-most (less padding
    than 576 -> 640 lanes), and a program that gathers blocks from it first
    re-lays the whole pool and lays it back at the end: 2 x 1.06 GB a step at
    32 slots of 4,096 (my chip run, PR 33). A family whose pool is large
    gives its rows this width (zeros behind the latent row) and keeps the
    layout it indexes."""
    return -(-width // 128) * 128


@stage("attn_proj")
def mla_latent(h, p, cfg, rope=None, width=None):
    """The cache row of each token: ``[RMSNorm(c); R_t k_r]``, [..., 576].
    ``rope``: ``(cos, sin)`` of the tokens' positions, or None for a family
    that does not rotate. ``width``: the pool's row width where it is more
    than the latent row's (zeros fill the rest)."""
    ckv = h @ p["wkva"].astype(cfg.dtype)
    c, k_r = jnp.split(ckv, [cfg.kv_lora_rank], axis=-1)
    if rope is not None:
        k_r = rotate(k_r, *rope)
    parts = [_rms_norm(c, p["kv_norm"], cfg.rms_eps), k_r]
    fill = (width or 0) - cfg.kv_lora_rank - k_r.shape[-1]
    if fill > 0:
        parts.append(jnp.zeros((*k_r.shape[:-1], fill), k_r.dtype))
    return jnp.concatenate(parts, axis=-1)


@stage("attn_proj")
def mla_query(h, p, cfg, rope=None):
    """``[q_n; R_t q_r]`` per head, [..., H, d_n + d_r]: through the low-rank
    pair and its norm where the layer has one, else through one matrix."""
    dt = cfg.dtype
    if "wq_a" in p:
        c_q = _rms_norm(h @ p["wq_a"].astype(dt), p["q_norm"], cfg.rms_eps)
        q = c_q @ p["wq_b"].astype(dt)
    else:
        q = h @ p["wq"].astype(dt)
    q = q.reshape(*h.shape[:-1], cfg.n_head, -1)
    if rope is None:
        return q
    dn = cfg.qk_nope_head_dim
    cos, sin = (a[..., None, :] for a in rope)  # one angle for every head
    return jnp.concatenate([q[..., :dn], rotate(q[..., dn:], cos, sin)], axis=-1)


def mla_scale(cfg, scale=None) -> float:
    """The softmax's scale: a family's own, or ``(d_n + d_r)^-1/2``."""
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 if scale is None else scale


def mla_prefill(
    h, ckv, l: int, table, pos, n_keys, p, cfg, *, block_size: int,
    rope=None, scale=None, key_positions: int = KEY_POSITIONS,
):
    """``h`` [T, D] normed queries at consecutive positions ``pos`` [T];
    ``ckv`` the latent pool [L, N, block, 576 or wider], which already holds
    their own rows, read at layer ``l`` through ``table`` [W]; ``n_keys``
    (traced) the positions that hold a row by now, so that nothing behind
    them is read. Keys and values are expanded per head a stretch of
    ``key_positions`` of the table at a time, scored in one product over
    ``[k_n; k_r]`` under the mask ``column <= position``, and folded into a
    running softmax (float32 maximum, sum and values): what the expanded
    attention over the whole table gives, without its scores. The queries go
    ``key_positions`` at a time too, each such run through the stretches up
    to its own last position only: a prompt that starts at 0 scores 10 of
    the 16 tiles of the 2,048 bucket."""
    T = h.shape[0]
    H, dn, dv = cfg.n_head, cfg.qk_nope_head_dim, cfg.v_head_dim
    R, dr, dt = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.dtype
    nb = math.gcd(table.shape[0], max(1, key_positions // block_size))  # blocks a step
    Kb = nb * block_size
    Qb = Kb if T % Kb == 0 else T  # queries a run
    q = mla_query(h, p, cfg, rope)
    scale = mla_scale(cfg, scale)
    with stage("attn_core"):
        wkvb = p["wkvb"].astype(dt)

    @stage("attn_core")  # the expansion of a stretch's keys and values per head is the fold's
    def attend(q, pos):
        def step(j, carry):
            m, s_sum, acc = carry
            blocks = jax.lax.dynamic_slice_in_dim(table, j * nb, nb)
            rows = ckv[l, blocks].reshape(Kb, -1)
            kv = (rows[:, :R] @ wkvb).reshape(Kb, H, dn + dv)
            k_r = jnp.broadcast_to(rows[:, None, R : R + dr], (Kb, H, dr))  # one for all heads
            k = jnp.concatenate([kv[..., :dn], k_r], axis=-1)
            s = jnp.einsum("thd,shd->hts", q, k, preferred_element_type=_F32) * scale
            cols = j * Kb + jnp.arange(Kb)
            s = jnp.where((cols[None, :] <= pos[:, None])[None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            keep = jnp.exp(m - m_new)
            e = jnp.exp(s - m_new[..., None])
            acc = acc * keep[..., None] + jnp.einsum(
                "hts,shd->htd", e.astype(dt), kv[..., dn:], preferred_element_type=_F32
            )
            return m_new, s_sum * keep + jnp.sum(e, axis=-1), acc

        # Every query sees column 0, so the first stretch leaves no row without
        # a key and a later stretch that is masked whole for a row adds
        # exp(-1e30). The run's last query sees the most.
        steps = (jnp.minimum(n_keys - 1, pos[-1]) // Kb + 1).astype(jnp.int32)
        n = q.shape[0]
        init = (jnp.full((H, n), -1e30, _F32), jnp.zeros((H, n), _F32), jnp.zeros((H, n, dv), _F32))
        _, s_sum, acc = jax.lax.fori_loop(0, steps, step, init)
        return (acc / s_sum[..., None]).astype(dt).transpose(1, 0, 2)

    o = [attend(q[i : i + Qb], pos[i : i + Qb]) for i in range(0, T, Qb)]
    with stage("attn_proj"):
        return jnp.concatenate(o).reshape(T, H * dv) @ p["wo"].astype(dt)


def mla_decode(h, ckv, l: int, tables, lengths, p, cfg, attend, rope=None):
    """One query a row: ``h`` [B, D] against the first ``lengths`` [B] rows
    that ``tables`` [B, W] give each slot in layer ``l`` of the latent pool
    ``ckv`` [L, N, block, 576 or wider]. ``wkvb`` is absorbed: its key half
    into the query, its value half into the output, so attention runs over
    latent rows as they lie in the pool (a wider row's zeros meet zeros in
    the query): ``attend`` is :func:`ray_tpu.models.paged.latent_decode_attention`'s,
    over the live blocks in place or over the gathered table, and carries
    the softmax's scale."""
    B = h.shape[0]
    H, dn, dv, R = cfg.n_head, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    dt = cfg.dtype
    q = mla_query(h, p, cfg, rope)
    with stage("attn_proj"):
        wkvb = p["wkvb"].astype(dt).reshape(R, H, dn + dv)
        q_lat = jnp.einsum("bhd,rhd->bhr", q[..., :dn], wkvb[..., :dn])
        ql = jnp.concatenate([q_lat, q[..., dn:]], axis=-1)  # [B, H, 576]
        ql = jnp.pad(ql, ((0, 0), (0, 0), (0, ckv.shape[-1] - ql.shape[-1])))
    with stage("attn_core"):
        o_lat = attend(ql, ckv, l, tables, lengths)  # [B, H, R]
    with stage("attn_proj"):
        o = jnp.einsum("bhr,rhd->bhd", o_lat, wkvb[..., dn:])
        return o.reshape(B, H * dv) @ p["wo"].astype(dt)


# ---------------------------------------------------------------------------
# Expert feed-forward


@stage("router")
def route(h, p, cfg):
    """``(experts [T, k] int32, weights [T, k] float32)`` of each token: the
    router in float32 over all experts of the model; chosen by score (plus the
    selection bias where the layer has one) among the experts of the
    ``topk_group`` best of ``n_group`` groups of consecutive experts, a group
    scored by the sum of its two largest; weighted by score, renormalised over
    the chosen and scaled. One group: a plain top-k."""
    s = jax.nn.sigmoid(jnp.dot(
        h.astype(_F32), p["router"].astype(_F32), precision=jax.lax.Precision.HIGHEST
    ))
    choice = s + p["router_bias"].astype(_F32) if "router_bias" in p else s
    G = cfg.n_group
    if G > 1:
        grouped = choice.reshape(*choice.shape[:-1], G, -1)
        best = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # [T, G]
        _, gid = jax.lax.top_k(best, cfg.topk_group)
        kept = jnp.any(gid[..., None] == jnp.arange(G), axis=-2)  # [T, G]
        choice = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(choice.shape)
    _, idx = jax.lax.top_k(choice, cfg.experts_per_token)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.renormalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w * cfg.routed_scaling


def moe_ffn(h, p, cfg, valid=None):
    """``h`` [T, D] normed -> ``(y [T, D], counts int32 [2], picks [T, k])``:
    the experts held here on the picks that land on them, plus the shared
    expert where the layer has one. ``valid`` [T] bool marks real tokens: the others are routed
    nowhere, so they touch no expert. ``counts`` is (picks that landed on a
    held expert, held experts with at least one pick).

    The (token, pick) pairs are sorted by expert, those that land elsewhere
    behind every group. Up to ``ROWS_A_PASS`` pairs (a decode step, a short
    prompt) go through the grouped products whole. More of them go
    ``ROWS_A_PASS`` sorted rows a pass, for as many passes as hold a pair that
    landed here: a chip that holds a sixteenth of the experts computes a
    sixteenth of a long prompt's rows, and drops none.

    What the layer's parameters hold decides its form (module docstring): with
    ``latent_in`` / ``latent_out`` the routed experts run on ``h @ latent_in``
    and their weighted sum goes back through ``latent_out``; without ``e_gate``
    (``s_gate``) the routed (shared) experts have no gate.

    The grouped products are :func:`ray_tpu.ops.moe_gmm.gmm`, which reads each
    touched expert's weights once, where :func:`experts_in_kernel` says so
    (a TPU, the experts' two widths in whole lane tiles), and
    ``jax.lax.ragged_dot`` elsewhere: the same contract, rows behind the last
    group never read unmasked here either way."""
    T = h.shape[0]
    E, k = cfg.experts_held, cfg.experts_per_token
    dt = cfg.dtype
    act = ACTIVATIONS[cfg.hidden_act]
    idx, w = route(h, p, cfg)
    with stage("router"):
        local = idx - cfg.expert_offset
        here = (local >= 0) & (local < E)
        if valid is not None:
            here &= valid[:, None]
        # Sort the (token, pick) pairs by expert; those that land elsewhere get
        # the number past the last expert and so sort behind every group.
        expert = jnp.where(here, local, E).reshape(T * k)
        order = jnp.argsort(expert, stable=True)
        sizes = jnp.sum(
            expert[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :], axis=0, dtype=jnp.int32
        )
    shared_in = h
    with stage("experts"):
        up, down = p["e_up"].astype(dt), p["e_down"].astype(dt)
        gate = p["e_gate"].astype(dt) if "e_gate" in p else None
        if "latent_in" in p:  # the routed experts' input; the router has read h itself
            h = h @ p["latent_in"].astype(dt)
    D = h.shape[1]
    product = moe_gmm.gmm if experts_in_kernel(p, dt) else jax.lax.ragged_dot

    @stage("experts")
    def experts(xs, sizes, weight=None):
        if gate is None:
            mid = act(product(xs, up, sizes))
        else:
            mid = act(product(xs, gate, sizes)) * product(xs, up, sizes)
        if weight is not None:  # a pick's weight, put on its row before the down projection
            mid = (mid.astype(_F32) * weight[:, None]).astype(dt)
        return product(mid, down, sizes)

    if T * k <= ROWS_A_PASS:
        with stage("router"):
            xs = h[order // k]
        ys = experts(xs, sizes)  # [T k, D], grouped by expert
        with stage("router"):
            # Back to (token, pick) order; a row behind the groups holds nothing.
            ys = ys[jnp.argsort(order)].reshape(T, k, D).astype(_F32)
            y = jnp.sum(jnp.where(here[..., None], ys * w[..., None], 0.0), axis=1)
    else:
        rows = ROWS_A_PASS
        with stage("router"):
            order = jnp.pad(order, (0, -(T * k) % rows))
            weight = jnp.pad(w.reshape(T * k), (0, order.shape[0] - T * k))
            ends = jnp.cumsum(sizes)
            landed = ends[-1]

        def one_pass(j, y):
            with stage("router"):
                lo = j * rows
                pairs = jax.lax.dynamic_slice_in_dim(order, lo, rows)
                real = lo + jnp.arange(rows) < landed
                part = jnp.clip(jnp.minimum(ends, lo + rows) - jnp.maximum(ends - sizes, lo), 0)
                keep, xs, weights = real[:, None], h[pairs // k], weight[pairs]
            ys = experts(xs, part, weights)
            with stage("router"):
                ys = jnp.where(keep, ys, 0)
                # Each row is added to its token through a 0/1 matrix (exact in
                # bfloat16, summed in float32): no scatter.
                to_token = real[:, None] & (pairs[:, None] // k == jnp.arange(T)[None, :])
                return y + jnp.einsum("rt,rd->td", to_token.astype(dt), ys, preferred_element_type=_F32)

        with stage("router"):
            passes, y0 = -(-landed // rows), jnp.zeros((T, D), _F32)
        y = jax.lax.fori_loop(0, passes, one_pass, y0)
    with stage("experts"):
        y = y.astype(dt)
        if "latent_out" in p:
            y = y @ p["latent_out"].astype(dt)
        mid = None
        if "s_up" in p:  # the shared expert, where the layer has one
            mid = shared_in @ p["s_up"].astype(dt)
            mid = act(shared_in @ p["s_gate"].astype(dt)) * mid if "s_gate" in p else act(mid)
    with stage("router"):
        counts = jnp.stack([jnp.sum(here, dtype=jnp.int32), jnp.sum(sizes > 0, dtype=jnp.int32)])
    with stage("experts"):
        return (y if mid is None else y + mid @ p["s_down"].astype(dt)), counts, idx


def experts_in_kernel(p, dtype, mesh=None) -> bool:
    """Whether :func:`moe_ffn` runs the grouped products of the expert layer
    ``p`` (its ``e_up`` [E, K, N] and ``e_down`` [E, N, K]) in ``dtype``
    through the kernel: :func:`ray_tpu.ops.moe_gmm.fits` of both directions."""
    _, K, N = p["e_up"].shape
    return moe_gmm.fits(K, N, dtype, mesh) and moe_gmm.fits(N, K, dtype, mesh)


def grouped_products_in_kernel(params, cfg, mesh=None):
    """:func:`experts_in_kernel` of a model with these parameters (every
    expert layer of a model has one shape): the arm its programs are built
    with. None for a model without an expert layer."""
    for p in params.get("layers", ()):
        if "e_up" in p:
            return experts_in_kernel(p, cfg.dtype, mesh)
    return None


def ffn(x, p, cfg, layer: int, valid, seen: list):
    """The feed-forward sublayer with its residual; an expert layer's
    counts and picks are appended to ``seen``."""
    if not cfg.is_moe(layer):
        return _mlp_sublayer(x, p, cfg)
    with stage("experts"):  # the norm feeds the router and the experts alike
        h = _rms_norm(x, p["mlp_norm"], cfg.rms_eps)
    y, counts, picks = moe_ffn(h, p, cfg, valid)
    seen.append((counts, picks))
    with stage("experts"):
        return x + y


def outputs(pool, logits, seen, with_picks: bool):
    """What a paged program of these families returns: the pool, the logits,
    the expert layers' counters [expert layers, 2] and, asked for, the picks;
    from a model with no expert layer, the pool and the logits alone."""
    if not seen:
        return pool, logits
    with stage("embed_head"):  # the packed counters
        counts = jnp.stack([c for c, _ in seen])
        if with_picks:
            return pool, logits, counts, jnp.stack([p for _, p in seen])
    return pool, logits, counts


@stage("embed_head")
def final_logits(params, last, cfg):
    h = _rms_norm(last, params["final_norm"], cfg.rms_eps)
    return (h @ params["lm_head"].astype(cfg.dtype)).astype(_F32)


# ---------------------------------------------------------------------------
# The selection bias of a served checkpoint


def balance_routers(params, key, cfg, rounds: int, tokens: int, init_pool, paged_prefill):
    """``params`` with each expert layer's ``router_bias`` set by the published
    rule of balancing without an auxiliary loss: round after round over
    seeded random tokens, the bias of an expert that got less than its share
    of the picks goes up by a step and that of one that got more goes down.
    A trained checkpoint is served with a bias that has balanced its experts;
    random weights with a zero bias are not balanced at all (an activation's
    positive mean gives every hidden state a common part, so every token
    favours the same few experts, and which chip's share they fall into changes
    with the seed: PERF.md section 6, PR 29). The bias enters the selection
    only. ``init_pool`` and ``paged_prefill`` are the family's own (its
    prefill gives the picks with ``with_picks``); to be called under ``jit``."""
    bs = 16
    table = jnp.arange(1, tokens // bs + 1, dtype=jnp.int32)
    pool = init_pool(cfg, tokens // bs + 1, bs, 0)
    at = [n for n, p in enumerate(params["layers"]) if "router_bias" in p]
    length, start = jnp.asarray(tokens, jnp.int32), jnp.asarray(0, jnp.int32)

    def with_biases(biases):
        layers = list(params["layers"])
        for n, b in zip(at, biases):
            layers[n] = {**layers[n], "router_bias": b}
        return {**params, "layers": layers}

    def one_round(r, biases):
        toks = jax.random.randint(jax.random.fold_in(key, r), (1, tokens), 0, cfg.vocab_size)
        *_, picks = paged_prefill(
            with_biases(biases), toks, length, start, table, pool, cfg,
            block_size=bs, with_picks=True,
        )
        load = jnp.mean(jax.nn.one_hot(picks, cfg.n_experts, dtype=_F32), axis=(1, 2))
        step = 0.02 * (1.0 - r / rounds)  # of a score in (0, 1); shrinking, so it settles
        return biases + step * jnp.sign(1.0 / cfg.n_experts - load)

    biases = jnp.stack([params["layers"][n]["router_bias"] for n in at])
    return with_biases(jax.lax.fori_loop(0, rounds, one_round, biases))


# ---------------------------------------------------------------------------
# What the engine writes on a span


def span_fields(cfg, counts, tokens: int, decode=None) -> dict:
    """What the engine writes on the span of one program run over ``tokens``
    real tokens: the program's counters (flat, as read back; two a layer,
    anything behind them is padding) summed over the expert layers. For a
    decode step, ``decode`` is ``(the live slots' positions, the rows the
    program reads a layer)``: ``latent_rows_live`` is what the step's
    attention needs (each live slot's ``position + 1`` rows),
    ``latent_rows_read`` what the arm the program was built with reads: each
    live slot's live blocks under the kernel, every slot's whole table under
    the gather."""
    counts = counts[: 2 * cfg.n_moe_layers].reshape(-1, 2)
    out = {
        "picks": tokens * cfg.experts_per_token * cfg.n_moe_layers,
        "picks_here": int(counts[:, 0].sum()),
        "experts_touched": int(counts[:, 1].sum()),
        "experts_held": cfg.experts_held * cfg.n_moe_layers,
    }
    if decode is not None:
        positions, rows_read = decode
        out["latent_rows_read"] = int(rows_read)
        out["latent_rows_live"] = int(positions.sum()) + len(positions)
    return out
