"""Paged (block-table) KV cache: serving memory management, TPU-native.

Reference parity: the capability vLLM supplies under ray.llm — paged
attention over a shared block pool (engine knobs at
python/ray/llm/_internal/serve/engines/vllm/vllm_models.py:89). Redesigned
for XLA's static-shape compilation model instead of CUDA paged-attention
kernels:

- **The pool** is a pytree ``{"k","v": [L, N_blocks, KH, block, Dh]}``.
  A request owns a *block table* — ``[W]`` int32 physical block ids with
  ``W = max_seq // block`` — so HBM is allocated per ~block tokens
  actually used, not per ``max_seq`` slot row. Block 0 is a reserved
  scratch block: padded/garbage writes land there and are never read.
- **Scatter, then attend.** New K/V are scattered straight into their
  (layer, block, offset) homes before anything reads them. *Prefill and
  verify* then gather the request's blocks back into a dense
  ``[KH, S, Dh]`` row (a *transient* — XLA frees it after the layer) and
  run the same masked grouped-head einsums as the training forward
  (``gpt2.forward``, ``llama.forward``). *Decode* reads the live blocks
  where they lie: on a TPU, at shapes that tile, one kernel a layer walks
  each slot's table and attends its ``ceil((position + 1) / block)`` live
  blocks (``ops/paged_attention.py``), so a step costs what is cached and
  not ``max_seq`` a slot; elsewhere decode gathers too, and that gather is
  what the tests hold the kernel to. Identical math (bf16 operands,
  float32 scores and softmax, the mask ``col <= position``) ⇒ logit
  parity with the training forward position by position, which the tests
  assert of both.
- **The pool is written in place.** The layer scan carries the whole pool
  and scans over the layer index, so each layer's scatter writes a few
  rows into the buffer it was handed; the only slab-sized work left is
  prefill's gather. A caller that donates the pool (the engine, the speculative
  decoder) gets its own buffer back as the output and must rebind it; one
  that does not (``benchmarks/check.py``) keeps its input and pays one
  copy of the pool at entry, which the compiler inserts.
- **Static shapes everywhere**: W, block, and the prefill bucket are
  compile-time constants; positions/tables are traced operands. Two
  compiled programs (prefill-per-bucket + decode).
- **Prefix sharing is free**: a pooled prefix is a list of block ids; a
  hit points the new request's first P/block table entries at the shared
  blocks (host-side refcount) — no device copy at all.

Family dispatch is by the configuration's ``family`` name, looked up once
(:func:`family`). GPT-2 (learned-position MHA) and Llama (RoPE GQA) share
everything here — scatter, gather, masking, grouped attention — and each
supplies a small hook table (``kv_hooks``), because GQA with group=1 *is*
MHA. A family whose cache is not keys and values per head supplies its cache
and its layer bodies itself:

- **What a pool is now.** Blocks of keys and values, as above, or latent
  rows in blocks (``"ckv": [L, N, block, 576]``: one row a position for all
  heads, no head axis, under the same block tables and the same
  ``BlockManager``), and for ``kimi_linear`` a recurrent state and a
  convolution tail *per slot* beside them (``"state": [L_kda, slots + 1, H,
  d_k, d_v]`` float32, ``"conv"``), which no block table reaches. The third
  shape is ``nemotron_h``'s: keys and values per head in blocks, as above
  (``"k"``, ``"v"``, one layer of the pool an attention block), *and* a state
  and a tail per slot (``"state": [L_mamba, slots + 1, H, P, N]`` float32,
  ``"conv"``). Its attention blocks write, gather and attend with this
  module's functions (``_write_read``, :func:`decode_attention`: the kernel
  over live blocks on a TPU); for everything else it is a family with a state
  per slot. ``solar_open2`` keeps the same three things, its state the delta
  rule's (``"state": [L_kda, slots + 1, H, d, d]``) and its attention layers
  read by prefill a stretch of the table at a time (:func:`prefill_attention`).
  Two facts about such a family, which the engine asks one by one:
  *it brings its own programs* (no ``kv_hooks``: its module supplies
  ``init_pool``, ``paged_prefill``, ``paged_decode`` and ``span_fields``),
  so nothing that reads ``pool["k"]`` or scores through the hooks serves it
  (speculative verification, the disaggregated handoff, tensor
  parallelism); and *it keeps a state per slot* (:func:`has_recurrent_state`).
  Latent rows alone (``mla_moe``) are a cache like keys and values: stale
  rows are masked away by position, a prefix is shared by block ids, a
  prompt prefills in chunks, and decode attends each slot's live blocks in
  place where the rows are whole lane tiles
  (:func:`latent_decode_attention`: the kernel's latent arm, one copy of a
  block serving keys and values alike). A state is not: a stale one is not masked, so a
  prefill from position 0 starts from zero state and a later chunk continues
  from its slot's; row ``slots`` is scratch, where slots that are free or
  still prefilling step; and a prefix hit would need the state at the
  prefix's end, so such a family is served without the prefix cache.
  The fourth shape is ``afmoe``'s: keys and values per head in blocks, in
  *two parts* (``{"full": {"k", "v"}, "window": {"k", "v"}}``, each ``[layers
  of the kind, blocks of the part, KH, block, Dh]``), because its layers are
  of two kinds: a full layer keeps every position, a window layer only the
  last ``sliding_window``. Each part has its own blocks and each slot a table
  for each (``tables [..., kinds, W]``: entry ``i`` of either is the block of
  positions ``[i block, (i + 1) block)``; behind the window a window table
  points at the scratch block, its blocks given back while the request runs).
  That is the third fact the engine asks (:func:`retention`): *how long each
  of its layer kinds keeps a position*. A table of one kind serves both where
  nothing was given back (``tables [..., W]``: a rehearsal, the routers'
  balance). Decode attends a window layer through the same
  :func:`decode_attention` with ``window`` given (the kernel's walk from the
  block that holds ``length - window``, the gather under the same mask), and
  prefill reads either kind a stretch of the table at a time
  (:func:`prefill_attention`). A prefix hit would need the window blocks
  behind the prefix's end, which are gone: served without the prefix cache.
"""

from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp

from ray_tpu.ops import paged_attention

Params = dict

# Family name -> its module: the one place a family is looked up by name.
_FAMILIES = {
    "gpt2": "ray_tpu.models.gpt2",
    "llama": "ray_tpu.models.llama",
    "kimi_linear": "ray_tpu.models.kimi_linear",
    "mla_moe": "ray_tpu.models.mla_moe",
    "nemotron_h": "ray_tpu.models.nemotron_h",
    "afmoe": "ray_tpu.models.afmoe",
    "solar_open2": "ray_tpu.models.solar_open2",
}


def family(cfg):
    """The module of the configuration's family. It supplies ``init_params``,
    ``param_logical_specs`` where the family has sharding rules, and either
    ``kv_hooks(cfg, S)`` (keys and values per head: the pool and the
    programs below serve it) or a cache and programs of its own
    (``init_pool``, ``paged_prefill``, ``paged_decode``, ``span_fields``,
    ``has_recurrent_state``).

    ``kv_hooks`` returns ``(embed, qkv, finish, final, H, KH, Dh)``. The
    hooks take ``pos2d``, always [B, T] absolute positions — prefill passes
    ``start + arange(T)`` broadcast over one row, decode passes per-slot
    ``positions[:, None]``; the same hooks serve both."""
    name = _FAMILIES.get(cfg.family)
    if name is None:
        raise ValueError(
            f"no paged programs for the family {cfg.family!r} "
            f"(known: {', '.join(_FAMILIES)})"
        )
    return importlib.import_module(name)


def brings_own_programs(cfg) -> bool:
    """Whether the family serves through a cache and programs of its own
    and not through ``kv_hooks`` (module docstring)."""
    return not hasattr(family(cfg), "kv_hooks")


def has_recurrent_state(cfg) -> bool:
    """Whether part of the family's cache is a state per slot that block
    tables do not reach (module docstring)."""
    return getattr(family(cfg), "has_recurrent_state", False)


def retention(cfg) -> tuple:
    """How many positions each of the family's layer kinds keeps, a kind an
    entry: None for a kind that keeps every position (the one kind of every
    family but one), a count for a kind that attends the last so many only.
    The first kind keeps everything. A slot holds a block table a kind
    (module docstring, the fourth shape)."""
    kinds = getattr(family(cfg), "retention", None)
    return (None,) if kinds is None else kinds(cfg)


def window_blocks_a_slot(window: int, span: int, block_size: int) -> int:
    """The most blocks of a window kind that one slot holds at a time: the
    window and the ``span`` positions of the longest prefill program looking
    back on it, in blocks, and one for a window that starts inside a block."""
    return -(-(window + span) // block_size) + 1


def init_block_pool(cfg, num_blocks: int, block_size: int, slots=None, window_blocks=None):
    """Zeroed pool pytree {"k","v"}: [L, N, KH, block, Dh] in activation
    dtype. KH is the KV-head count (unexpanded GQA for Llama). A family with
    a state per slot sizes it for ``slots`` sequences (the engine's
    ``max_slots``) and a scratch row. ``window_blocks``: the blocks of each
    window kind's part, from the engine that counted them (None: the family
    counts them from what its configuration says of the deployment)."""
    mod = family(cfg)
    if not hasattr(mod, "kv_hooks"):
        if window_blocks is not None:
            return mod.init_pool(cfg, num_blocks, block_size, slots, window_blocks=window_blocks)
        return mod.init_pool(cfg, num_blocks, block_size, slots)
    shape = (cfg.n_layer, num_blocks, _kv_heads(cfg), block_size, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
    }


# ---------------------------------------------------------------------------
# Paged ops


def _write(pool_kv, l, bids, offs, new):
    """Layer ``l`` of one pool tensor [L, N, KH, block, Dh]: scatter
    ``new`` [..., KH, Dh] to the (block, offset) homes ``bids`` / ``offs``
    [...]. Indexed by (layer, block) at once: slicing the layer out first
    would bring its whole slab back as a temporary."""
    khi = jnp.arange(pool_kv.shape[2])
    return pool_kv.at[l, bids[..., None], khi, offs[..., None]].set(new)


def _write_read(pool_kv, l, bids, offs, new, tables):
    """:func:`_write`, then gather the rows of ``tables`` [..., W] back as
    [..., W, KH, block, Dh], by (layer, block) at once as well."""
    pool_kv = _write(pool_kv, l, bids, offs, new)
    return pool_kv, pool_kv[l, tables]


def _attend_gathered(qg, pk, pv, l, tables, lengths, window=None):
    """Decode attention by gather: each slot's whole table brought back as
    a dense row [B, KH, S, Dh] and masked to its first ``lengths[b]``
    positions (with ``window``, the last ``window`` of them). ``qg`` [B, KH,
    group, Dh]; returns the same shape. What
    :func:`ops.paged_attention.paged_decode_attention` computes from the
    live blocks alone."""
    B, KH, _, Dh = qg.shape
    S = tables.shape[1] * pk.shape[3]
    kd = pk[l, tables].transpose(0, 2, 1, 3, 4).reshape(B, KH, S, Dh)
    vd = pv[l, tables].transpose(0, 2, 1, 3, 4).reshape(B, KH, S, Dh)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, kd).astype(jnp.float32)
    s = s * (1.0 / (Dh**0.5))
    mask = jnp.arange(S)[None, :] < lengths[:, None]  # [B, S]
    if window is not None:
        mask &= jnp.arange(S)[None, :] >= lengths[:, None] - window
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    pa = jax.nn.softmax(s, axis=-1).astype(vd.dtype)
    return jnp.einsum("bkgs,bksd->bkgd", pa, vd)


def _attend_latent_gathered(ql, ckv, l, tables, lengths, *, value_width, scale):
    """Latent decode attention by gather: each slot's whole table brought
    back as dense rows [B, S, C] and masked to its first ``lengths[b]``
    positions. ``ql`` [B, H, C], the absorbed query; returns [B, H,
    value_width]. What
    :func:`ops.paged_attention.paged_latent_decode_attention` computes from
    the live blocks alone."""
    B = ql.shape[0]
    S = tables.shape[1] * ckv.shape[2]
    rows = ckv[l, tables].reshape(B, S, ckv.shape[3])
    s = jnp.einsum("bhc,bsc->bhs", ql, rows).astype(jnp.float32) * scale
    mask = jnp.arange(S)[None, :] < lengths[:, None]  # [B, S]
    pa = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), axis=-1).astype(rows.dtype)
    return jnp.einsum("bhs,bsr->bhr", pa, rows[..., :value_width])


def decode_attends_in_place(cfg, block_size: int, *, mesh=None) -> bool:
    """Whether the family's decode program, lowered for this process's
    default backend, attends the live blocks in place (the kernel) or gathers
    each table whole: the kernel on a TPU, for a cache whose shapes are whole
    TPU tiles and fit VMEM, outside a mesh (the compiler cannot partition a
    Mosaic call). The cache is keys and values per head, which ``kv_hooks``
    serve and which a family that brings its own programs says it keeps
    (``kv_per_head``: its attention layers then call
    :func:`decode_attention`, the same choice, for their part of the pool),
    or latent rows, whose width the configuration gives (``pool_row_dim``:
    the family's programs call :func:`latent_decode_attention`).
    Decided by what the code can see, like
    ``ops.attention.uses_flash_kernel``; nothing a user sets reaches it."""
    mod = family(cfg)
    if hasattr(mod, "kv_hooks") or getattr(mod, "kv_per_head", False):
        fits = _kernel_fits(cfg, block_size, mesh)
    else:
        fits = hasattr(cfg, "pool_row_dim") and _latent_kernel_fits(cfg, block_size, mesh)
    return jax.default_backend() == "tpu" and fits


def _kv_heads(cfg) -> int:
    return getattr(cfg, "n_kv_head", None) or cfg.n_head


def _kernel_fits(cfg, block_size, mesh) -> bool:
    return (mesh is None or mesh.size == 1) and paged_attention.fits(
        _kv_heads(cfg), cfg.head_dim, block_size, jnp.dtype(cfg.dtype).itemsize
    )


def _latent_kernel_fits(cfg, block_size, mesh) -> bool:
    return (mesh is None or mesh.size == 1) and paged_attention.fits_latent(
        cfg.n_head, cfg.pool_row_dim, cfg.kv_lora_rank, block_size,
        jnp.dtype(cfg.dtype).itemsize,
    )


def _choose(kernel, gather, fits: bool, interpret: bool):
    """``kernel`` where the shapes fit and the program is lowered for a TPU
    (decided at lowering, so a program compiled here for a described chip
    holds what the chip will run), ``gather`` elsewhere. ``interpret`` runs
    the kernel in the Pallas interpreter whatever the platform and the
    shapes (the tests)."""
    if interpret:
        return functools.partial(kernel, interpret=True)
    if not fits:
        return gather
    return functools.partial(jax.lax.platform_dependent, tpu=kernel, default=gather)


def decode_attention(cfg, block_size, mesh, interpret, window=None):
    """The decode step's attention over the scattered pool of keys and
    values per head, ``attend(qg, pk, pv, l, tables, lengths)``: the kernel
    or the gather, as :func:`_choose` says. ``window``: for a layer that
    attends the last ``window`` positions only, either arm under that mask;
    None leaves both as they were."""
    kernel, gather = paged_attention.paged_decode_attention, _attend_gathered
    if window is not None:
        kernel = functools.partial(kernel, window=window)
        gather = functools.partial(gather, window=window)
    return _choose(kernel, gather, _kernel_fits(cfg, block_size, mesh), interpret)


# Positions of the table that one step of prefill's running softmax scores,
# and queries that go through it together: [KH, group, 512, 512] float32 is
# 50 MB at 48 heads, where a 2,048-token chunk against a table of 18,432 is
# 7.2 GB a layer.
KEY_POSITIONS = 512


def prefill_attention(
    q, pk, pv, l, table, pos, n_keys, *, block_size: int, window=None,
    key_positions: int = KEY_POSITIONS,
):
    """Prefill's attention over keys and values per head, a stretch of the
    table at a time: ``q`` [T, KH, group, Dh] at consecutive positions ``pos``
    [T] against layer ``l`` of ``pk`` / ``pv`` [L, N, KH, block, Dh], which
    already hold the queries' own keys and values, read through ``table``
    [W]; ``n_keys`` (traced) the positions that hold a row by now. The mask is
    ``column <= position`` and, with ``window``, ``position - column <
    window``. Each run of ``key_positions`` queries folds the stretches from
    the one that holds the first column its first query sees (column 0
    without a window) to the one that holds its last query's own position
    into a running softmax (float32 maximum, sum and values), as
    :func:`ray_tpu.models.latent_moe.mla_prefill` does for latent rows: no
    ``[heads, T, table]`` scores exist, and a window layer reads nothing
    behind its window, where the table points at the scratch block. Returns
    [T, KH, group, Dh] in the pool's dtype."""
    T, KH, G, Dh = q.shape
    dt = pk.dtype
    nb = math.gcd(table.shape[0], max(1, key_positions // block_size))  # blocks a step
    Kb = nb * block_size
    Qb = Kb if T % Kb == 0 else T  # queries a run
    scale = Dh**-0.5
    f32 = jnp.float32

    def attend(q, pos):
        def step(j, carry):
            m, s_sum, acc = carry
            blocks = jax.lax.dynamic_slice_in_dim(table, j * nb, nb)
            k = pk[l, blocks].transpose(1, 0, 2, 3).reshape(KH, Kb, Dh)
            v = pv[l, blocks].transpose(1, 0, 2, 3).reshape(KH, Kb, Dh)
            s = jnp.einsum("tkgd,ksd->kgts", q, k, preferred_element_type=f32) * scale
            cols = j * Kb + jnp.arange(Kb)
            seen = cols[None, :] <= pos[:, None]
            if window is not None:
                seen &= cols[None, :] > pos[:, None] - window
            s = jnp.where(seen[None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            keep = jnp.exp(m - m_new)
            e = jnp.exp(s - m_new[..., None])
            acc = acc * keep[..., None] + jnp.einsum(
                "kgts,ksd->kgtd", e.astype(dt), v, preferred_element_type=f32
            )
            return m_new, s_sum * keep + jnp.sum(e, axis=-1), acc

        # A stretch that is masked whole for a row adds exp(0) a column to it
        # while the row's maximum is still -1e30; the first stretch that
        # holds a column it sees multiplies that away (exp(-1e30 - m) = 0),
        # and every row sees its own position, in the run's last stretches.
        last = jnp.minimum(n_keys - 1, pos[-1]) // Kb
        first = 0 if window is None else jnp.minimum(jnp.maximum(pos[0] - window + 1, 0) // Kb, last)
        n = q.shape[0]
        init = (
            jnp.full((KH, G, n), -1e30, f32), jnp.zeros((KH, G, n), f32),
            jnp.zeros((KH, G, n, Dh), f32),
        )
        _, s_sum, acc = jax.lax.fori_loop(first, last + 1, step, init)
        return (acc / s_sum[..., None]).astype(dt).transpose(2, 0, 1, 3)

    return jnp.concatenate([attend(q[i : i + Qb], pos[i : i + Qb]) for i in range(0, T, Qb)])


def latent_decode_attention(cfg, block_size, mesh, interpret, scale: float):
    """The same over a pool of latent rows ``[L, N, block, pool_row_dim]``,
    ``attend(ql, ckv, l, tables, lengths)`` -> [B, H, kv_lora_rank]: ``ql``
    [B, H, pool_row_dim] the absorbed query, ``scale`` the softmax's."""
    static = dict(value_width=cfg.kv_lora_rank, scale=scale)
    return _choose(
        functools.partial(paged_attention.paged_latent_decode_attention, **static),
        functools.partial(_attend_latent_gathered, **static),
        _latent_kernel_fits(cfg, block_size, mesh), interpret,
    )


def _scan_layers(body, x, params, pool):
    """Run ``body`` over the layers with the pool in the carry, so that
    layer l's scatter writes into the buffer layer l+1 reads: a scanned
    input and a stacked output are two buffers, and cost a slab copy a
    layer each way."""
    L = pool["k"].shape[0]
    (x, pk, pv), _ = jax.lax.scan(
        body,
        (x, pool["k"], pool["v"]),
        (params["blocks"], jnp.arange(L, dtype=jnp.int32)),
    )
    return x, {"k": pk, "v": pv}


def paged_prefill(
    params: Params,
    tokens: jax.Array,  # [1, T] int32 — suffix tokens (whole prompt if
    #                      start == 0), left-aligned in a static bucket
    length: jax.Array,  # scalar int32 — true suffix token count (<= T)
    start: jax.Array,  # scalar int32 — cached-prefix length (block-aligned;
    #                     0 for a fresh prompt). Traced: no recompile per
    #                     prefix length.
    table: jax.Array,  # [W] int32 block table for this request
    pool,
    cfg,
    *,
    block_size: int,
    slot=None,  # scalar int32 — a family with a state per slot: the
    #             sequence's row of it (None: the scratch row)
):
    """Prefill positions [start, start+T) into the pool; return
    (pool, last_logits [vocab] f32), and a third value, its counters, from
    a family that has some.

    The one prefill program serves both the fresh path (start=0) and the
    prefix-continue path — attention always spans the full gathered row
    under the mask ``col <= start + row`` (the static-shape trade)."""
    mod = family(cfg)
    if not hasattr(mod, "kv_hooks"):
        return mod.paged_prefill(
            params, tokens, length, start, table, pool, cfg,
            block_size=block_size, slot=slot,
        )
    B, T = tokens.shape
    W = table.shape[0]
    S = W * block_size
    embed, qkv, finish, final, H, KH, Dh = mod.kv_hooks(cfg, S)
    group = H // KH

    pos = start + jnp.arange(T, dtype=jnp.int32)  # [T]
    x = embed(params, tokens, pos[None])
    bids = table[pos // block_size]  # [T] physical blocks to write
    offs = pos % block_size
    cols = jnp.arange(S)
    mask = cols[None, :] <= pos[:, None]  # [T, S]
    scale = 1.0 / (Dh**0.5)

    def body(carry, layer):
        x, pk, pv = carry  # pk/pv: the whole pool, [L, N, KH, block, Dh]
        p, l = layer
        q, k, v = qkv(x, p, pos[None])  # q [1,H,T,Dh], k/v [1,KH,T,Dh]
        kt = k[0].transpose(1, 0, 2)  # [T, KH, Dh]
        vt = v[0].transpose(1, 0, 2)
        # This request's row (transient): [W,KH,block,Dh] -> [KH,S,Dh]
        pk, kd = _write_read(pk, l, bids, offs, kt, table)
        pv, vd = _write_read(pv, l, bids, offs, vt, table)
        kd = kd.transpose(1, 0, 2, 3).reshape(KH, S, Dh)
        vd = vd.transpose(1, 0, 2, 3).reshape(KH, S, Dh)
        qg = q[0].reshape(KH, group, T, Dh)
        s = jnp.einsum("kgtd,ksd->kgts", qg, kd).astype(jnp.float32) * scale
        s = jnp.where(mask[None, None], s, -1e30)
        pa = jax.nn.softmax(s, axis=-1).astype(vd.dtype)
        attn = jnp.einsum("kgts,ksd->kgtd", pa, vd).reshape(1, H, T, Dh)
        return (finish(x, attn, p), pk, pv), None

    x, pool = _scan_layers(body, x, params, pool)
    last = jax.lax.dynamic_index_in_dim(
        x[0], (length - 1).astype(jnp.int32), axis=0, keepdims=False
    )
    logits = final(params, last[None])[0]
    return pool, logits


def paged_verify(
    params: Params,
    tokens: jax.Array,  # [B, T] int32 — token t of row b sits at absolute
    #                      position positions[b] + t
    positions: jax.Array,  # [B] int32 — first write position per slot
    tables: jax.Array,  # [B, W] int32
    pool,
    cfg,
    *,
    block_size: int,
):
    """Multi-token decode: score T consecutive tokens per slot in ONE
    forward — the target-model verification pass of speculative decoding
    (and a strict generalization of :func:`paged_decode`, which is the
    T=1 case). Returns (pool, logits [B, T, vocab] f32): logits[b, t] is
    the next-token distribution after consuming tokens[b, t].

    Callers must keep positions + T <= max_seq (the engine falls back to
    plain decode near the boundary): out-of-range scatter indices would
    clamp into the slot's last real block and corrupt it."""
    if has_recurrent_state(cfg):
        raise ValueError(
            f"paged_verify cannot serve the family {cfg.family!r}: rejected "
            "tokens would have to be taken back out of its recurrent state"
        )
    if brings_own_programs(cfg):
        raise ValueError(
            f"paged_verify cannot serve the family {cfg.family!r}: it scores "
            "through kv_hooks, and the family brings programs of its own"
        )
    B, T = tokens.shape
    W = tables.shape[1]
    S = W * block_size
    embed, qkv, finish, final, H, KH, Dh = family(cfg).kv_hooks(cfg, S)
    group = H // KH

    pos2d = positions[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    x = embed(params, tokens, pos2d)  # [B, T, D]
    rows = jnp.arange(B)
    bids = tables[rows[:, None], pos2d // block_size]  # [B, T]
    offs = pos2d % block_size
    cols = jnp.arange(S)
    mask = cols[None, None, :] <= pos2d[:, :, None]  # [B, T, S]
    scale = 1.0 / (Dh**0.5)

    def body(carry, layer):
        x, pk, pv = carry  # pk/pv: the whole pool, [L, N, KH, block, Dh]
        p, l = layer
        q, k, v = qkv(x, p, pos2d)  # q [B,H,T,Dh], k/v [B,KH,T,Dh]
        kt = k.transpose(0, 2, 1, 3)  # [B, T, KH, Dh]
        vt = v.transpose(0, 2, 1, 3)
        pk, kd = _write_read(pk, l, bids, offs, kt, tables)
        pv, vd = _write_read(pv, l, bids, offs, vt, tables)
        kd = kd.transpose(0, 2, 1, 3, 4).reshape(B, KH, S, Dh)
        vd = vd.transpose(0, 2, 1, 3, 4).reshape(B, KH, S, Dh)
        qg = q.reshape(B, KH, group, T, Dh)
        s = jnp.einsum("bkgtd,bksd->bkgts", qg, kd).astype(jnp.float32)
        s = jnp.where(mask[:, None, None], s * scale, -1e30)
        pa = jax.nn.softmax(s, axis=-1).astype(vd.dtype)
        attn = jnp.einsum("bkgts,bksd->bkgtd", pa, vd).reshape(B, H, T, Dh)
        return (finish(x, attn, p), pk, pv), None

    x, pool = _scan_layers(body, x, params, pool)
    D = x.shape[-1]
    logits = final(params, x.reshape(B * T, D)).reshape(B, T, -1)
    return pool, logits


def paged_decode(
    params: Params,
    last_tokens: jax.Array,  # [B] int32
    positions: jax.Array,  # [B] int32 — write position per slot
    tables: jax.Array,  # [B, W] int32 — per-slot block tables
    pool,
    cfg,
    *,
    block_size: int,
    live=None,  # [B] bool — a family with a state per slot: which slots
    #             hold a decoding sequence (None: all)
    mesh=None,  # the mesh the operands are sharded over, if any
    interpret: bool = False,  # the attention kernel in the Pallas
    #             interpreter, whatever the platform and shapes (tests)
):
    """One token per slot against the shared pool; returns
    (pool, logits [B, vocab] f32), and a third value, its counters, from a
    family that has some. Free slots must point their table at
    the scratch block (id 0) so their garbage writes never land in a
    block another request owns.

    Each layer scatters the step's key and value, then attends positions
    [0, position] of every slot: over the live blocks in place or over the
    gathered table (:func:`decode_attention`)."""
    mod = family(cfg)
    if not hasattr(mod, "kv_hooks"):
        return mod.paged_decode(
            params, last_tokens, positions, tables, pool, cfg,
            block_size=block_size, live=live, interpret=interpret,
        )
    B = last_tokens.shape[0]
    W = tables.shape[1]
    S = W * block_size
    embed, qkv, finish, final, H, KH, Dh = mod.kv_hooks(cfg, S)
    group = H // KH
    attend = decode_attention(cfg, block_size, mesh, interpret)

    x = embed(params, last_tokens[:, None], positions[:, None])  # [B,1,D]
    rows = jnp.arange(B)
    bids = tables[rows, positions // block_size]  # [B]
    offs = positions % block_size
    lengths = positions + 1  # the step's own key is attended

    def body(carry, layer):
        x, pk, pv = carry  # pk/pv: the whole pool, [L, N, KH, block, Dh]
        p, l = layer
        q, k, v = qkv(x, p, positions[:, None])  # [B,{H,KH},1,Dh]
        pk = _write(pk, l, bids, offs, k[:, :, 0, :])
        pv = _write(pv, l, bids, offs, v[:, :, 0, :])
        qg = q[:, :, 0, :].reshape(B, KH, group, Dh)
        attn = attend(qg, pk, pv, l, tables, lengths).reshape(B, H, 1, Dh)
        return (finish(x, attn, p), pk, pv), None

    x, pool = _scan_layers(body, x, params, pool)
    logits = final(params, x[:, 0, :])
    return pool, logits
