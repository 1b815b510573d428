"""GPT-2 autoregressive inference: KV cache, prefill, single-token decode.

The training path (:mod:`ray_tpu.models.gpt2`) recomputes full-sequence
attention; serving needs O(1) work per generated token. This module adds the
static-shape KV-cache path the LLM tier's engine drives:

- the cache is a pytree of [L, B, H, S_max, Dh] arrays (slot-batched:
  row b is one request slot, reusable across requests — continuous
  batching's invariant);
- ``prefill`` runs the prompt through flash/causal attention once and writes
  k/v for positions [0, T);
- ``decode_step`` embeds one token per slot at its own position, scatters
  its k/v into the cache, and attends over the masked prefix.

Everything is shape-static (pad to S_max) so each of the two programs
compiles exactly once. Reference parity: the reference delegates this to
vLLM (python/ray/llm/_internal/serve/engines/vllm/); here it is
framework-native JAX.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.models.gpt2 import GPT2Config, _layer_norm
from ray_tpu.ops.attention import causal_attention

Params = dict


def init_kv_cache(cfg: GPT2Config, n_slots: int, max_seq: int | None = None):
    """Zeroed cache pytree: {"k","v"}: [L, B, H, S, Dh] in activation dtype."""
    S = max_seq or cfg.max_seq
    shape = (cfg.n_layer, n_slots, cfg.n_head, S, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
    }


def _qkv(x, p, cfg):
    B, T, D = x.shape
    h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])
    qkv = h @ p["qkv_w"].astype(cfg.dtype) + p["qkv_b"].astype(cfg.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(B, T, cfg.n_head, cfg.head_dim).transpose(0, 2, 1, 3)

    return heads(q), heads(k), heads(v)


def _finish_block(x, attn, p, cfg):
    B, H, T, Dh = attn.shape
    attn = attn.transpose(0, 2, 1, 3).reshape(B, T, H * Dh)
    x = x + attn @ p["proj_w"].astype(cfg.dtype) + p["proj_b"].astype(cfg.dtype)
    h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"])
    h = h @ p["fc_w"].astype(cfg.dtype) + p["fc_b"].astype(cfg.dtype)
    h = jax.nn.gelu(h, approximate=True)
    return x + h @ p["fc2_w"].astype(cfg.dtype) + p["fc2_b"].astype(cfg.dtype)


def prefill(
    params: Params,
    tokens: jax.Array,  # [B, T] int32, left-aligned, padded with anything
    lengths: jax.Array,  # [B] true prompt lengths (<= T)
    cache,
    cfg: GPT2Config,
    mesh=None,
):
    """Process prompts, fill cache[: , :, :T], return (cache, last_logits).

    last_logits[b] is the logits after token lengths[b]-1 — what the first
    sampled token conditions on. ``mesh`` is the mesh the params are sharded
    over (the engine's tp mesh), if any.
    """
    if cfg.n_experts > 0:
        raise NotImplementedError("decode path is dense-GPT2 only")
    B, T = tokens.shape
    x = params["wte"].astype(cfg.dtype)[tokens]
    x = x + params["wpe"].astype(cfg.dtype)[:T][None]

    def body(x, p):
        q, k, v = _qkv(x, p, cfg)
        attn = causal_attention(q, k, v, impl=cfg.attn_impl, mesh=mesh)
        return _finish_block(x, attn, p, cfg), (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
    # ks: [L, B, H, T, Dh] -> write positions [0, T)
    cache = {
        "k": cache["k"].at[:, :, :, :T, :].set(ks),
        "v": cache["v"].at[:, :, :, :T, :].set(vs),
    }
    x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    last = jnp.take_along_axis(
        x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]  # [B, D]
    logits = (
        last @ params["wte"].astype(cfg.dtype).T
    ).astype(jnp.float32)
    return cache, logits


def prefill_continue(
    params: Params,
    tokens: jax.Array,  # [B, T] int32 — the tokens AFTER the cached prefix
    lengths: jax.Array,  # [B] true new-token counts (<= T)
    start: jax.Array,  # scalar int32 — cached prefix length (cache rows
    #                    [0, start) are already valid for these slots)
    cache,
    cfg: GPT2Config,
):
    """Prefill positions [start, start+T) on top of an existing cache
    prefix — the prefix-caching fast path: a shared system prompt's KV is
    copied into the slot once and only the suffix pays prefill FLOPs.

    ``start`` is a *traced* scalar (no recompile per prefix length): each
    new token attends over the full static cache row with a mask
    ``col <= start + row`` — O(T * S_max) scores instead of O(T * (start+T)),
    the static-shape trade this engine makes everywhere.
    Returns (cache, last_logits) like :func:`prefill`.
    """
    if cfg.n_experts > 0:
        raise NotImplementedError("decode path is dense-GPT2 only")
    B, T = tokens.shape
    S = cache["k"].shape[3]
    x = params["wte"].astype(cfg.dtype)[tokens]
    pos = start + jnp.arange(T)
    x = x + params["wpe"].astype(cfg.dtype)[pos][None]

    cols = jnp.arange(S)
    rows = jnp.arange(T)
    # token row r (absolute position start+r) sees cache cols <= start+r
    mask = cols[None, :] <= (start + rows)[:, None]  # [T, S]
    scale = 1.0 / (cfg.head_dim**0.5)

    def body(x, layer):
        p, ck, cv = layer  # ck/cv: [B, H, S, Dh]
        q, k, v = _qkv(x, p, cfg)  # [B, H, T, Dh]
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k, start, axis=2)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v, start, axis=2)
        s = jnp.einsum("bhtd,bhsd->bhts", q, ck).astype(jnp.float32) * scale
        s = jnp.where(mask[None, None], s, -1e30)
        pattn = jax.nn.softmax(s, axis=-1).astype(cv.dtype)
        attn = jnp.einsum("bhts,bhsd->bhtd", pattn, cv)
        return _finish_block(x, attn, p, cfg), (ck, cv)

    x, (ks, vs) = jax.lax.scan(
        lambda c, lyr: body(c, lyr),
        x,
        (params["blocks"], cache["k"], cache["v"]),
    )
    cache = {"k": ks, "v": vs}
    x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    last = jnp.take_along_axis(
        x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]
    logits = (last @ params["wte"].astype(cfg.dtype).T).astype(jnp.float32)
    return cache, logits


def decode_step(
    params: Params,
    last_tokens: jax.Array,  # [B] int32 — token generated at positions-1
    positions: jax.Array,  # [B] int32 — where last_tokens goes in the cache
    cache,
    cfg: GPT2Config,
):
    """One token per slot: write kv at ``positions``, attend over the
    prefix, return (cache, logits [B, vocab] f32)."""
    B = last_tokens.shape[0]
    S = cache["k"].shape[3]
    H, Dh = cfg.n_head, cfg.head_dim
    x = params["wte"].astype(cfg.dtype)[last_tokens]  # [B, D]
    x = x + params["wpe"].astype(cfg.dtype)[positions]
    x = x[:, None, :]  # [B, 1, D]

    rows = jnp.arange(B)
    cols = jnp.arange(S)
    # Slot b may attend to cache positions <= positions[b].
    mask = cols[None, :] <= positions[:, None]  # [B, S]
    scale = 1.0 / (Dh**0.5)

    def body(x, layer):
        p, ck, cv = layer  # ck/cv: [B, H, S, Dh]
        q, k, v = _qkv(x, p, cfg)  # q/k/v: [B, H, 1, Dh]
        ck = ck.at[rows[:, None], jnp.arange(H)[None, :], positions[:, None]].set(
            k[:, :, 0, :]
        )
        cv = cv.at[rows[:, None], jnp.arange(H)[None, :], positions[:, None]].set(
            v[:, :, 0, :]
        )
        s = jnp.einsum("bhd,bhsd->bhs", q[:, :, 0, :], ck).astype(
            jnp.float32
        ) * scale
        s = jnp.where(mask[:, None, :], s, -1e30)
        pattn = jax.nn.softmax(s, axis=-1).astype(cv.dtype)
        attn = jnp.einsum("bhs,bhsd->bhd", pattn, cv)[:, :, None, :]
        return _finish_block(x, attn, p, cfg), (ck, cv)

    x, (ks, vs) = jax.lax.scan(
        lambda c, lyr: body(c, lyr),
        x,
        (params["blocks"], cache["k"], cache["v"]),
    )
    cache = {"k": ks, "v": vs}
    x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])[:, 0]
    logits = (x @ params["wte"].astype(cfg.dtype).T).astype(jnp.float32)
    return cache, logits
