"""Llama autoregressive inference: GQA KV cache, RoPE-aware prefill/decode.

Serving twin of :mod:`ray_tpu.models.gpt2_decode` for the Llama family.
The cache stores the n_kv_head heads UNEXPANDED — GQA's serving win:
[L, B, KH, S, Dh] is n_head/n_kv_head times smaller than an MHA cache, so
more slots fit HBM. Decode attention groups query heads against their KV
head with a reshape (no repeat materialization):

    q [B, KH, group, Dh] x cache_k [B, KH, S, Dh] -> scores [B, KH, group, S]

Positions are traced scalars (RoPE tables sliced dynamically), so the
prefix-cache continue path compiles once per suffix bucket like GPT-2's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import (
    LlamaConfig,
    _apply_rope,
    _mlp_sublayer,
    _rms_norm,
    rope_tables,
)
from ray_tpu.ops.attention import causal_attention

Params = dict


def init_kv_cache(cfg: LlamaConfig, n_slots: int, max_seq: int | None = None):
    """Zeroed cache: {"k","v"}: [L, B, KV_HEADS, S, Dh] (unexpanded GQA)."""
    S = max_seq or cfg.max_seq
    shape = (cfg.n_layer, n_slots, cfg.n_kv_head, S, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
    }


def _qkv_rope(x, p, cfg: LlamaConfig, cos, sin):
    """x [B, T, D] -> (q [B,H,T,Dh], k [B,KH,T,Dh], v [B,KH,T,Dh]),
    q/k rotary-rotated with the given tables ([T, half])."""
    B, T, D = x.shape
    H, KH, Dh = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    h = _rms_norm(x, p["attn_norm"], cfg.rms_eps)
    q = h @ p["wq"].astype(cfg.dtype)
    k = h @ p["wk"].astype(cfg.dtype)
    v = h @ p["wv"].astype(cfg.dtype)

    def heads(t, n):
        return t.reshape(B, T, n, Dh).transpose(0, 2, 1, 3)

    return (
        _apply_rope(heads(q, H), cos, sin),
        _apply_rope(heads(k, KH), cos, sin),
        heads(v, KH),
    )


def _expand_kv(t, group: int):
    """[B, KH, S, Dh] -> [B, KH*group, S, Dh] (prefill-time expansion for
    the flash kernel; decode avoids it via grouped einsums)."""
    return jnp.repeat(t, group, axis=1)


def prefill(
    params: Params,
    tokens: jax.Array,  # [B, T] int32
    lengths: jax.Array,  # [B]
    cache,
    cfg: LlamaConfig,
    mesh=None,
):
    """Fill cache[:, :, :, :T]; return (cache, last_logits [B, vocab]).
    ``mesh`` is the mesh the params are sharded over, if any."""
    B, T = tokens.shape
    group = cfg.n_head // cfg.n_kv_head
    x = params["wte"].astype(cfg.dtype)[tokens]
    cos, sin = rope_tables(cfg, T)

    def body(x, p):
        q, k, v = _qkv_rope(x, p, cfg, cos, sin)
        attn = causal_attention(
            q, _expand_kv(k, group), _expand_kv(v, group),
            impl=cfg.attn_impl, mesh=mesh,
        )
        attn = attn.transpose(0, 2, 1, 3).reshape(B, T, cfg.d_model)
        x = x + attn @ p["wo"].astype(cfg.dtype)
        return _mlp_sublayer(x, p, cfg), (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
    cache = {
        "k": cache["k"].at[:, :, :, :T, :].set(ks),
        "v": cache["v"].at[:, :, :, :T, :].set(vs),
    }
    x = _rms_norm(x, params["final_norm"], cfg.rms_eps)
    last = jnp.take_along_axis(
        x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]
    logits = (last @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)
    return cache, logits


def prefill_continue(
    params: Params,
    tokens: jax.Array,  # [B, T] — the tokens AFTER the cached prefix
    lengths: jax.Array,  # [B] true new-token counts
    start: jax.Array,  # scalar int32 — cached prefix length (traced)
    cache,
    cfg: LlamaConfig,
):
    """Prefill positions [start, start+T) over an existing cache prefix
    (prefix-cache fast path; see gpt2_decode.prefill_continue — same
    static-shape trade: scores span the full cache row under a mask)."""
    B, T = tokens.shape
    S = cache["k"].shape[3]
    KH, Dh = cfg.n_kv_head, cfg.head_dim
    group = cfg.n_head // KH
    x = params["wte"].astype(cfg.dtype)[tokens]
    cos_full, sin_full = rope_tables(cfg, S)
    half = Dh // 2
    cos = jax.lax.dynamic_slice(cos_full, (start, 0), (T, half))
    sin = jax.lax.dynamic_slice(sin_full, (start, 0), (T, half))

    cols = jnp.arange(S)
    rows = jnp.arange(T)
    mask = cols[None, :] <= (start + rows)[:, None]  # [T, S]
    scale = 1.0 / (Dh**0.5)

    def body(x, layer):
        p, ck, cv = layer  # ck/cv: [B, KH, S, Dh]
        q, k, v = _qkv_rope(x, p, cfg, cos, sin)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k, start, axis=2)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v, start, axis=2)
        # Grouped attention without expanding the cache: fold the group
        # into the query-head axis.
        qg = q.reshape(B, KH, group, T, Dh)
        s = (
            jnp.einsum("bkgtd,bksd->bkgts", qg, ck).astype(jnp.float32)
            * scale
        )
        s = jnp.where(mask[None, None, None], s, -1e30)
        pattn = jax.nn.softmax(s, axis=-1).astype(cv.dtype)
        attn = jnp.einsum("bkgts,bksd->bkgtd", pattn, cv)
        attn = attn.reshape(B, cfg.n_head, T, Dh)
        attn = attn.transpose(0, 2, 1, 3).reshape(B, T, cfg.d_model)
        x = x + attn @ p["wo"].astype(cfg.dtype)
        return _mlp_sublayer(x, p, cfg), (ck, cv)

    x, (ks, vs) = jax.lax.scan(
        lambda c, lyr: body(c, lyr),
        x,
        (params["blocks"], cache["k"], cache["v"]),
    )
    cache = {"k": ks, "v": vs}
    x = _rms_norm(x, params["final_norm"], cfg.rms_eps)
    last = jnp.take_along_axis(
        x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]
    logits = (last @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)
    return cache, logits


def decode_step(
    params: Params,
    last_tokens: jax.Array,  # [B]
    positions: jax.Array,  # [B]
    cache,
    cfg: LlamaConfig,
):
    """One token per slot with the grouped (unexpanded) cache."""
    B = last_tokens.shape[0]
    S = cache["k"].shape[3]
    H, KH, Dh = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    group = H // KH
    x = params["wte"].astype(cfg.dtype)[last_tokens][:, None, :]  # [B,1,D]
    cos_full, sin_full = rope_tables(cfg, S)
    half = Dh // 2
    # Per-slot position rotation tables: [B, 1, half].
    cos = cos_full[positions][:, None]
    sin = sin_full[positions][:, None]

    rows = jnp.arange(B)
    cols = jnp.arange(S)
    mask = cols[None, :] <= positions[:, None]  # [B, S]
    scale = 1.0 / (Dh**0.5)

    def rope1(t):  # [B, n, 1, Dh] with per-batch tables
        t1, t2 = jnp.split(t, 2, axis=-1)
        c = cos[:, None, :, :].astype(t.dtype)  # [B,1,1,half]
        s = sin[:, None, :, :].astype(t.dtype)
        return jnp.concatenate([t1 * c - t2 * s, t1 * s + t2 * c], axis=-1)

    def body(x, layer):
        p, ck, cv = layer  # [B, KH, S, Dh]
        h = _rms_norm(x, p["attn_norm"], cfg.rms_eps)
        q = h @ p["wq"].astype(cfg.dtype)
        k = h @ p["wk"].astype(cfg.dtype)
        v = h @ p["wv"].astype(cfg.dtype)
        q = rope1(q.reshape(B, 1, H, Dh).transpose(0, 2, 1, 3))  # [B,H,1,Dh]
        k = rope1(k.reshape(B, 1, KH, Dh).transpose(0, 2, 1, 3))
        v = v.reshape(B, 1, KH, Dh).transpose(0, 2, 1, 3)
        ck = ck.at[
            rows[:, None], jnp.arange(KH)[None, :], positions[:, None]
        ].set(k[:, :, 0, :])
        cv = cv.at[
            rows[:, None], jnp.arange(KH)[None, :], positions[:, None]
        ].set(v[:, :, 0, :])
        qg = q[:, :, 0, :].reshape(B, KH, group, Dh)
        s = (
            jnp.einsum("bkgd,bksd->bkgs", qg, ck).astype(jnp.float32)
            * scale
        )
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        pattn = jax.nn.softmax(s, axis=-1).astype(cv.dtype)
        attn = jnp.einsum("bkgs,bksd->bkgd", pattn, cv)
        attn = attn.reshape(B, 1, H * Dh)
        x = x + attn @ p["wo"].astype(cfg.dtype)
        return _mlp_sublayer(x, p, cfg), (ck, cv)

    x, (ks, vs) = jax.lax.scan(
        lambda c, lyr: body(c, lyr),
        x,
        (params["blocks"], cache["k"], cache["v"]),
    )
    cache = {"k": ks, "v": vs}
    x = _rms_norm(x, params["final_norm"], cfg.rms_eps)[:, 0]
    logits = (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)
    return cache, logits
