"""Paged (block-table) KV cache: serving memory management, TPU-native.

Reference parity: the capability vLLM supplies under ray.llm (engine knobs at
python/ray/llm/_internal/serve/engines/vllm/vllm_models.py:89), redesigned
for XLA's static shapes instead of CUDA paged-attention kernels:

- **Block tables.** A request owns ``[W]`` int32 physical block ids, ``W =
  max_seq // block``: HBM goes by blocks used, not by ``max_seq`` a slot.
  Block 0 is scratch: padded and garbage writes land there and are never read.
  W, block and the prefill bucket are compile-time constants, positions and
  tables traced operands: two compiled programs (prefill per bucket, decode).
  A pooled prefix is a list of block ids, shared by host-side refcount.
- **Scatter, then attend.** New rows are scattered into their (layer, block,
  offset) homes before anything reads them. *Prefill and verify* gather the
  request's blocks back into a dense row (a transient) and run the training
  forward's masked grouped-head einsums. *Decode* reads the live blocks where
  they lie: on a TPU, at shapes that tile, one kernel a layer walks each
  slot's table over its ``ceil((position + 1) / block)`` live blocks
  (``ops/paged_attention.py``); elsewhere decode gathers too, and that gather
  is what the tests hold the kernel to. Identical math (bf16 operands, float32
  scores and softmax, ``col <= position``): logit parity position by position.
- **The pool is written in place.** The layer scan carries the whole pool. A
  caller that donates it (the engine, the speculative decoder) gets its buffer
  back as the output and must rebind it; one that does not pays one copy.

**What a pool is made of.** Four parts; which of them a family has is its
record's business (:class:`Cache`, :func:`cache`), and the engine reads that
and nothing else about a family's cache.

- *Blocks of rows per head*, ``{"k", "v": [L, N, KH, block, Dh]}``: written
  with :func:`_write` (a row of a head an update: decode, verify) or
  :func:`_write_blocks` (a block an update: a prefill's rows are consecutive
  whole blocks), read by prefill as a gathered table or where they lie
  (:func:`prefill_attention`: on a TPU one kernel a layer that walks the
  table, ``ops/paged_prefill_attention.py``; elsewhere a stretch of the
  table at a time), by decode through :func:`decode_attention`. What those
  two need of an attention layer is its *kind* (:class:`AttentionKind`: KV
  heads, the key's and the value's width, a window, a learned sink), which
  a family with one shape of head takes from its configuration
  (:func:`attention_kind`) and one whose kinds of layer differ in shape
  states on its record (``Cache.kinds``).
- *Blocks of latent rows*, ``"ckv": [L, N, block, pool_row_dim]``: one row a
  position for all heads, under the same tables and ``BlockManager``. A cache
  like keys and values (stale rows masked by position, prefixes shared,
  prompts in chunks); decode attends in place where the rows are whole lane
  tiles (:func:`latent_decode_attention`). A family with an indexer keeps
  its index keys beside them, ``"ikv"``, under the same table.
- *A state and a tail per slot*, ``"state": [L', slots + 1, ...]`` float32 and
  ``"conv": [L', slots + 1, K - 1, C]``, which no block table reaches: slot
  ``b``'s are row ``b``, row ``slots`` is scratch. A stale state is not masked,
  so one policy holds (:func:`state_prefill`, :func:`state_decode`): a
  sequence begins from zero whatever the slot held, a later chunk continues
  from the slot's, a decode step leaves a slot that is not live as it was.
  On a TPU, at tiles that are whole, a decode step holds a slot's tile in
  VMEM while it is stepped (``ops/state_step.py``): read once, written once;
  and a prefill of a delta rule's state (``Cache.delta_rule``) scans its
  chunks with the state held there too (``ops/delta_scan.py``).
- *A second table kind that keeps a window*: the blocks in two parts
  (``{"full": {"k", "v"}, "window": {"k", "v"}}``), a slot a table for each
  (``tables [..., kinds, W]``; behind the window a window table points at the
  scratch block, its blocks given back while the request runs). One table
  ``[..., W]`` serves both where nothing was given back (a rehearsal).

Neither a state nor the blocks behind a window are there at a prefix's end:
such a family is served without the prefix cache.

Family dispatch is by the configuration's ``family`` name (:func:`family`).
GPT-2 (learned-position MHA) and Llama (RoPE GQA) share everything here and
each supplies a small hook table (``kv_hooks``): GQA with group=1 *is* MHA;
speculative verification, the handoff and tensor parallelism reach these two
only (:meth:`Cache.why_not`). Every other family brings ``init_pool``, its
programs, ``span_fields`` and its record, ``cache(cfg)``, in its module.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.models.common import stage
from ray_tpu.ops import delta_scan, paged_attention, paged_prefill_attention, state_step

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
    "mimo_v2": "ray_tpu.models.mimo_v2",
    "granitemoehybrid": "ray_tpu.models.granite_hybrid",
    "deepseek_v32": "ray_tpu.models.deepseek_v32",
}


def family(cfg):
    """The module of the configuration's family. It supplies ``init_params``,
    ``param_logical_specs`` where the family has sharding rules, and either
    ``kv_hooks(cfg, S)`` (keys and values per head: the pool and the
    programs below serve it) or a cache and programs of its own
    (``init_pool``, ``paged_prefill``, ``paged_decode``, ``span_fields``,
    ``cache``).

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


@dataclasses.dataclass(frozen=True)
class Cache:
    """What a family keeps for a request (module docstring, "What a pool is
    made of")."""

    # Positions each table kind keeps, a kind an entry: None keeps every
    # position (the first kind always), a count the last so many only. A slot
    # holds a block table a kind.
    retention: tuple = (None,)
    slot_state: bool = False  # a state and a tail per slot beside the blocks
    # ... and the state is a gated delta rule's [H, d_k, d_v], whose prefill
    # scan has a kernel arm (:func:`state_prefill`)
    delta_rule: bool = False
    per_head: bool = True  # rows in blocks: keys and values per head, or latent rows
    hooks: bool = False  # served through kv_hooks by this module's programs
    # The kinds of attention layer over keys and values per head, a table
    # kind an entry (:class:`AttentionKind`, each with its count of layers),
    # where the family's kinds differ in shape (empty: one shape of head,
    # which the configuration gives: :func:`attention_kind`).
    kinds: tuple = ()
    # Prefill attends those layers through :func:`prefill_attention`, the
    # pool read where it lies (a family without gathers its table whole).
    prefill_in_place: bool = False
    # Decode attends rows an indexer chose one by one: no arm walks a table.
    selects_rows: bool = False

    @property
    def shares_prefixes(self) -> bool:
        """Whether a pooled prefix can serve a later request: a hit needs
        what the cache held at the prefix's end, and neither a state nor the
        blocks behind a window are kept."""
        return not self.slot_state and len(self.retention) == 1

    def why_not(self, family: str, what: str) -> Optional[str]:
        """Why ``what`` (speculative verification, tensor parallelism, the
        disaggregated handoff: each written for one pool of keys and values
        under one table, the cache that ``kv_hooks`` serve) cannot serve the
        family of this record, said field by field; None where it can."""
        if self.hooks:
            return None
        keeps = []
        if len(self.retention) > 1:
            keeps.append(
                "keeps a block table per layer kind and gives window blocks back "
                "while a request runs"
            )
        if self.slot_state:
            keeps.append("keeps a recurrent state per slot, which no block table reaches")
        rows = "keys and values per head" if self.per_head else "latent rows"
        keeps.append(f"brings its own paged programs over {rows}")
        return (
            f"the family {family!r} {', '.join(keeps)}, and {what} is served "
            "through kv_hooks only"
        )


def cache(cfg) -> Cache:
    """The record of the configuration's family: this module's for a family
    of ``kv_hooks``, else the one its module states."""
    mod = family(cfg)
    return Cache(hooks=True) if hasattr(mod, "kv_hooks") else mod.cache(cfg)


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
    if not cache(cfg).hooks:
        mod = family(cfg)
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


@stage("pool_write")
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
    with stage("attn_core"):
        return pool_kv, pool_kv[l, tables]


@stage("pool_write")
def _write_blocks(pool_kv, l, table, start, new, block_size: int):
    """:func:`_write` at a prefill's grain: ``new`` [T, KH, Dh] are the rows
    of ``T`` consecutive positions from ``start``, whole blocks of them
    (``start`` and ``T`` multiples of ``block_size``: the engine holds every
    prefill program to that), so each block of ``table`` [W] they fill is one
    update of ``KH x block x Dh`` contiguous elements and not ``block x KH``
    of a row each. The same values at the same homes as ``_write(pool_kv, l,
    table[pos // block_size], pos % block_size, new)``, the padding behind a
    last chunk included. A block's id is the table indexed at its number: a
    number past the table's end clamps entry by entry as a position's does
    there (``llm/engine.py:_chunk_bucket``), and several padded blocks may
    name the scratch block at once, so the ids are not unique."""
    T, KH, Dh = new.shape
    assert T % block_size == 0, (T, block_size)
    n = T // block_size
    blocks = table[start // block_size + jnp.arange(n, dtype=jnp.int32)]
    tiles = new.reshape(n, block_size, KH, Dh).transpose(0, 2, 1, 3)
    return pool_kv.at[l, blocks].set(tiles)


def _write_blocks_read(pool_kv, l, table, start, new, block_size: int):
    """:func:`_write_blocks`, then the rows of ``table`` [W] gathered back as
    [W, KH, block, Dh], as :func:`_write_read` does."""
    pool_kv = _write_blocks(pool_kv, l, table, start, new, block_size)
    with stage("attn_core"):
        return pool_kv, pool_kv[l, table]


def _attend_gathered(qg, pk, pv, l, tables, lengths, sink=None, *, window=None, scale=None):
    """Decode attention by gather: each slot's whole table brought back as
    dense rows [B, KH, S, Dk] and [B, KH, S, Dv] and masked to its first
    ``lengths[b]`` positions (with ``window``, the last ``window`` of them).
    ``qg`` [B, KH, group, Dk] (a key pool with wider rows holds zeros behind a
    key: the query is padded to meet them); ``sink`` [KH, group]: a column of
    the softmax that carries no value. Returns [B, KH, group, Dv]. What
    :func:`ops.paged_attention.paged_decode_attention` computes from the
    live blocks alone."""
    B, KH, _, Dh = qg.shape
    S = tables.shape[1] * pk.shape[3]
    if pk.shape[-1] > Dh:
        qg = jnp.pad(qg, ((0, 0),) * 3 + ((0, pk.shape[-1] - Dh),))
    kd = pk[l, tables].transpose(0, 2, 1, 3, 4).reshape(B, KH, S, pk.shape[-1])
    vd = pv[l, tables].transpose(0, 2, 1, 3, 4).reshape(B, KH, S, pv.shape[-1])
    scale = 1.0 / (Dh**0.5) if scale is None else scale
    return _attend_rows(qg, kd, vd, lengths, sink, window, scale)


def _attend_rows(qg, kd, vd, lengths, sink, window, scale):
    """A slot's gathered rows ``kd`` [B, KH, S, Dk] and ``vd`` [B, KH, S, Dv]
    attended by its query under the mask of its length (and window), with a
    sink's column where there is one; [B, KH, group, Dv]."""
    S = kd.shape[2]
    s = jnp.einsum("bkgd,bksd->bkgs", qg, kd).astype(jnp.float32)
    s = s * scale
    mask = jnp.arange(S)[None, :] < lengths[:, None]  # [B, S]
    if window is not None:
        mask &= jnp.arange(S)[None, :] >= lengths[:, None] - window
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    if sink is None:
        pa = jax.nn.softmax(s, axis=-1)
    else:
        column = jnp.broadcast_to(sink.astype(jnp.float32)[None, :, :, None], (*s.shape[:3], 1))
        pa = jax.nn.softmax(jnp.concatenate([s, column], axis=-1), axis=-1)[..., :S]
    return jnp.einsum("bkgs,bksd->bkgd", pa.astype(vd.dtype), vd)


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


@dataclasses.dataclass(frozen=True)
class AttentionKind:
    """What :func:`decode_attention`, :func:`prefill_attention` and the choice
    between the kernel and the gather need of one kind of attention layer
    over keys and values per head."""

    kv_heads: int
    key_width: int  # of a query's and a key's head: the scores' scale is its ^-1/2 unless stated
    value_width: int
    itemsize: int  # of the pool's dtype
    window: Optional[int] = None  # the last positions a query sees, its own included
    sink: bool = False  # a learned scalar a query head in the softmax's sum
    # The key pool's row width where it is more than a key's (zeros behind
    # the key, up to whole lane tiles; None: the key's own).
    key_lanes: Optional[int] = None
    # What the kernel's call is named after in a device trace, for a family
    # whose kinds a reader tells apart ("": the kernel's own name).
    name: str = ""
    layers: int = 0  # of the kind, held here (a family's record says it: ``Cache.kinds``)
    # The scores' scale where the family states one (a published multiplier
    # that replaces ``key_width^-1/2``; None: that).
    scale: Optional[float] = None
    # A head's value and key side by side in ONE pool row, ``{"kv": [L, N, KH,
    # block, value | key]}`` (``ops/paged_attention.py``, "Heads of half a
    # lane tile"): heads of 64, where a pool a side would pad every row to
    # twice its bytes or leave decode to the gather.
    packed: bool = False

    @property
    def row_bytes(self) -> int:
        """Bytes the mathematics needs for one position in all layers of the
        kind, whatever the pool pads."""
        return self.layers * self.kv_heads * (self.key_width + self.value_width) * self.itemsize


def attention_kind(cfg, window=None) -> AttentionKind:
    """The kind of a family whose attention layers have one shape of head:
    ``n_kv_head`` (or ``n_head``) heads of ``head_dim`` for keys and values
    alike, no sink; ``window`` for its layers that keep one."""
    return AttentionKind(
        _kv_heads(cfg), cfg.head_dim, cfg.head_dim, jnp.dtype(cfg.dtype).itemsize, window
    )


def decode_attends_in_place(cfg, block_size: int, *, mesh=None) -> bool:
    """Whether the family's decode program, lowered for this process's
    default backend, attends the live blocks in place (the kernel) or gathers
    each table whole: the kernel on a TPU, for a cache whose shapes are whole
    TPU tiles and fit VMEM, outside a mesh (the compiler cannot partition a
    Mosaic call). By the family's record the rows in blocks are keys and
    values per head (its attention layers call :func:`decode_attention`, the
    same choice, a kind of layer at a time: in place only if every kind's
    shapes fit) or latent rows, whose width the configuration gives
    (``pool_row_dim``: its programs call :func:`latent_decode_attention`).
    Decided by what the code can see; nothing a user sets reaches it. False
    for a family whose decode reads rows chosen one by one (``selects_rows``)."""
    record = cache(cfg)
    if jax.default_backend() != "tpu" or record.selects_rows:
        return False
    if not record.per_head:
        return _latent_kernel_fits(cfg, block_size, mesh)
    return all(_kernel_fits(kind, block_size, mesh) for kind in record.kinds or (attention_kind(cfg),))


def _kv_heads(cfg) -> int:
    return getattr(cfg, "n_kv_head", None) or cfg.n_head


def _kernel_fits(kind: AttentionKind, block_size, mesh) -> bool:
    one_chip = mesh is None or mesh.size == 1
    if kind.packed:
        return one_chip and paged_attention.fits_packed(
            kind.kv_heads, kind.key_width, block_size, kind.itemsize
        )
    return one_chip and paged_attention.fits(
        kind.kv_heads, kind.key_lanes or kind.key_width, block_size, kind.itemsize,
        kind.value_width,
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


def decode_attention(kind: AttentionKind, block_size, mesh, interpret):
    """The decode step's attention over the scattered pool of keys and
    values per head, ``attend(qg, pk, pv, l, tables, lengths)`` (and, for a
    kind with a sink, the layer's ``sink`` [KH, group] behind them): the
    kernel or the gather, as :func:`_choose` says; its caller opens the
    stage ``attn_core`` around it. A kind with a window
    attends the last ``window`` positions only, either arm under that mask;
    one without a window, a sink or a name leaves both as they were."""
    kernel, gather = paged_attention.paged_decode_attention, _attend_gathered
    if kind.window is not None:
        kernel = functools.partial(kernel, window=kind.window)
        gather = functools.partial(gather, window=kind.window)
    if kind.name:  # a suffix: a reader that matches the kernel's own name as a prefix still does
        kernel = functools.partial(kernel, name=f"paged_decode_attention_{kind.name}")
    if kind.scale is not None:
        kernel = functools.partial(kernel, scale=kind.scale)
        gather = functools.partial(gather, scale=kind.scale)
    return _choose(kernel, gather, _kernel_fits(kind, block_size, mesh), interpret)


def _attend_packed_gathered(qg, pool, l, tables, lengths, *, scale=None):
    """:func:`_attend_gathered` over one pool of ``[value | key]`` rows ``[L,
    N, KH, block, 2 Dh]``: what
    :func:`ops.paged_attention.paged_packed_decode_attention` computes from
    the live blocks alone."""
    B, KH, _, Dh = qg.shape
    S = tables.shape[1] * pool.shape[3]
    rows = pool[l, tables].transpose(0, 2, 1, 3, 4).reshape(B, KH, S, 2 * Dh)
    scale = Dh**-0.5 if scale is None else scale
    return _attend_rows(qg, rows[..., Dh:], rows[..., :Dh], lengths, None, None, scale)


def packed_decode_attention(kind: AttentionKind, block_size, mesh, interpret):
    """:func:`decode_attention` for a kind whose pool is ``packed``:
    ``attend(qg, pool, l, tables, lengths)`` over the one pool."""
    assert kind.packed and kind.window is None and not kind.sink, kind
    static = {} if kind.scale is None else {"scale": kind.scale}
    return _choose(
        functools.partial(paged_attention.paged_packed_decode_attention, **static),
        functools.partial(_attend_packed_gathered, **static),
        _kernel_fits(kind, block_size, mesh), interpret,
    )


# Positions of the table that one step of the fold's running softmax scores,
# and queries that go through it together: [KH, group, 512, 512] float32 is
# 50 MB at 48 heads, where a 2,048-token chunk against a table of 18,432 is
# 7.2 GB a layer.
KEY_POSITIONS = 512


@stage("attn_core")
def prefill_attention(
    q, pk, pv, l, table, pos, n_keys, *, block_size: int, window=None, sink=None,
    key_positions: int = KEY_POSITIONS, name: str = "", interpret: bool = False,
):
    """Prefill's attention over keys and values per head, the pool read where
    it lies: ``q`` [T, KH, group, Dk] at consecutive positions ``pos`` [T]
    against layer ``l`` of ``pk`` [L, N, KH, block, Dk or wider] and ``pv``
    [L, N, KH, block, Dv], which already hold the queries' own keys and
    values, read through ``table`` [W]; ``n_keys`` (traced) the positions
    that hold a row by now. The mask is ``column <= position`` and, with
    ``window``, ``position - column < window``; ``sink`` [KH, group] is the
    layer's learned sink, with which a row's running softmax starts from
    ``(sink, 1, 0)`` and not from ``(-1e30, 0, 0)``. No ``[heads, T, table]``
    scores exist, and a window layer reads nothing behind its window, where
    the table points at the scratch block. Returns [T, KH, group, Dv] in the
    pool's dtype (a row at or past ``n_keys``, the padding behind a last
    chunk: finite numbers that mean nothing).

    The choice between two arms, as :func:`decode_attention` is (``name``:
    the kind's, behind the kernel's own in a device trace; ``interpret``: the
    kernel in the Pallas interpreter, the tests): one kernel call over a grid
    of (KV head, tile of queries) that walks the table
    (:func:`ops.paged_prefill_attention.paged_prefill_attention`) where the
    program is lowered for a TPU and the kernel takes the kind's shapes
    (:func:`ops.paged_prefill_attention.fits`: a chunk's length in whole
    tiles, and no window longer than a tile of queries), and
    :func:`_prefill_fold`'s runs of XLA
    einsums elsewhere. The families that call this are served on one chip:
    there is no mesh to ask about."""
    fold = functools.partial(
        _prefill_fold, block_size=block_size, window=window, sink=sink, key_positions=key_positions
    )

    def kernel(q, pk, pv, l, table, pos, n_keys, interpret=False):
        return paged_prefill_attention.paged_prefill_attention(
            q, pk, pv, l, table, pos[0], n_keys, sink, window=window, interpret=interpret,
            name=f"paged_prefill_attention_{name}" if name else "paged_prefill_attention",
        )

    fits = paged_prefill_attention.fits(
        q.shape[0], q.shape[2], pk.shape[-1], pv.shape[-1], block_size, window
    )
    l, n_keys = jnp.asarray(l, jnp.int32), jnp.asarray(n_keys, jnp.int32)
    return _choose(kernel, fold, fits, interpret)(q, pk, pv, l, table, pos, n_keys)


def prefill_attends_in_kernel(cfg, block_size: int, tokens: int, *, mesh=None) -> bool:
    """Whether the family's prefill program of ``tokens`` positions, lowered
    for this process's default backend, attends through the kernel
    (:func:`prefill_attention`'s choice, a kind of layer at a time by its
    shapes: :func:`ops.paged_prefill_attention.fits`) in any of its kinds of
    layer. (Trinity's full layers do, and its layers with a window of two
    chunks keep the fold.) False for a family whose prefill does not call
    :func:`prefill_attention` at all (``Cache.prefill_in_place``), and
    under a mesh over chips, which no such family is served on."""
    record = cache(cfg)
    if jax.default_backend() != "tpu" or not record.prefill_in_place:
        return False
    if mesh is not None and mesh.size > 1:
        return False
    kinds = record.kinds or (
        attention_kind(cfg), *(attention_kind(cfg, w) for w in record.retention if w is not None)
    )
    return any(
        paged_prefill_attention.fits(
            tokens, cfg.n_head // kind.kv_heads, kind.key_lanes or kind.key_width, kind.value_width,
            block_size, kind.window,
        )
        for kind in kinds
    )


def _prefill_fold(
    q, pk, pv, l, table, pos, n_keys, *, block_size: int, window=None, sink=None,
    key_positions: int = KEY_POSITIONS,
):
    """:func:`prefill_attention` in XLA's own operations, a stretch of the
    table at a time. Each run of ``key_positions`` queries folds the
    stretches from the one that holds the first column its first query sees
    (column 0 without a window) to the one that holds its last query's own
    position into a running softmax (float32 maximum, sum and values), as
    :func:`ray_tpu.models.latent_moe.mla_prefill` does for latent rows."""
    T, KH, G, Dh = q.shape
    Dk, Dv = pk.shape[-1], pv.shape[-1]
    dt = pk.dtype
    nb = math.gcd(table.shape[0], max(1, key_positions // block_size))  # blocks a step
    Kb = nb * block_size
    Qb = Kb if T % Kb == 0 else T  # queries a run
    scale = Dh**-0.5
    f32 = jnp.float32
    if Dk > Dh:  # zeros behind a key in the pool's rows meet zeros in the query
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, Dk - Dh),))

    def attend(q, pos):
        def step(j, carry):
            m, s_sum, acc = carry
            blocks = jax.lax.dynamic_slice_in_dim(table, j * nb, nb)
            k = pk[l, blocks].transpose(1, 0, 2, 3).reshape(KH, Kb, Dk)
            v = pv[l, blocks].transpose(1, 0, 2, 3).reshape(KH, Kb, Dv)
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
        # (From a sink the maximum is never -1e30: such a stretch adds
        # exp(-1e30 - sink) = 0 from the start.)
        last = jnp.minimum(n_keys - 1, pos[-1]) // Kb
        first = 0 if window is None else jnp.minimum(jnp.maximum(pos[0] - window + 1, 0) // Kb, last)
        n = q.shape[0]
        if sink is None:
            m0, s0 = jnp.full((KH, G, n), -1e30, f32), jnp.zeros((KH, G, n), f32)
        else:
            m0 = jnp.broadcast_to(sink.astype(f32)[:, :, None], (KH, G, n))
            s0 = jnp.ones((KH, G, n), f32)
        init = (m0, s0, jnp.zeros((KH, G, n, Dv), f32))
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


def state_prefill(step, state, conv, l, slot, fresh, *, scan_rows=None, interpret: bool = False):
    """The prefill side of a state and a tail per slot: layer ``l`` of
    ``state`` [L', slots + 1, ...] and ``conv`` [L', slots + 1, K - 1, C]
    through the family's mixer, ``step(state0, tail0) -> (out, state1,
    tail1)``. ``slot``: the sequence's row (None: the scratch row, the
    last). ``fresh``: whether the sequence begins with this call (``start ==
    0``, worked out once a program): it then starts from zero and an empty
    tail whatever the slot held, and else from the slot's (a later chunk).
    Returns ``(out, state, conv)`` with the row written back.

    ``scan_rows``: given by a family whose state is a gated delta rule's
    ``[H, d_k, d_v]`` (``Cache.delta_rule``), the rows of the prefill: its
    step scans through :func:`ray_tpu.ops.delta_scan.kda`
    (``models/kda.py:kda_prefill``), whose chunked scan has two arms, chosen
    as :func:`_choose` says: where the program is lowered for a TPU and the
    rows and heads are the kernel's (:func:`prefill_scans_in_kernel`) ``state0`` is a
    :class:`ray_tpu.ops.delta_scan.Held`, from which that scans with the
    state and a chunk's values on the chip, one Pallas call a layer; an array,
    and the plain ``lax.scan``, elsewhere. ``interpret`` runs the kernel in
    the Pallas interpreter whatever the platform and the shapes (the tests)."""
    row = state.shape[1] - 1 if slot is None else slot
    with stage("state_scan"):
        state0 = jnp.where(fresh, 0.0, state[l, row])
        tail0 = jnp.where(fresh, 0, conv[l, row])

    def kernel(state0, tail0, interpret=False):
        return step(delta_scan.Held(state0, interpret), tail0)

    # the family's mixer names its own stages
    delta_rule = scan_rows is not None
    out, state1, tail1 = _choose(
        kernel, step, delta_rule and delta_scan.tiles(scan_rows, *state.shape[2:]),
        delta_rule and interpret,
    )(state0, tail0)
    with stage("state_scan"):
        return out, state.at[l, row].set(state1), conv.at[l, row].set(tail1.astype(conv.dtype))


def state_decode(step, state, conv, l, rows: int, keep=None, *, interpret: bool = False):
    """The decode side: slot ``b``'s state and tail are row ``b``, so rows
    ``[:rows]`` of layer ``l`` go through ``step(state0, tail0) -> (out,
    state1, tail1)`` and back where they lie, with no gather by slot. ``keep``
    [rows] bool (``~live``, worked out once a program; None: every slot is
    live): a slot that is not live, free or still prefilling in chunks, keeps
    its state and tail as they were; its ``out`` means nothing.

    ``state0`` is the rows' values ``[rows, ...]``, or, where the program is
    lowered for a TPU and the state's tiles are the kernel's
    (:func:`state_steps_in_kernel`), the rows where they lie, a
    :class:`ray_tpu.ops.state_step.Rows`: a family's step takes either
    through :func:`ray_tpu.ops.state_step.kda` / ``ssd``, which step an array
    in plain ``jax.numpy`` and a ``Rows`` in VMEM, a tile read once and
    written once, and hand back what they were given. ``interpret`` runs the
    kernel in the Pallas interpreter whatever the platform and the shapes (the
    tests). Returns ``(out, state, conv)``."""

    def plain(state, tail0):
        with stage("state_scan"):
            state0 = state[l, :rows]
        out, state1, tail1 = step(state0, tail0)  # the family's mixer names its own stages
        with stage("state_scan"):
            if keep is not None:
                state1 = jnp.where(keep[:, None, None, None], state0, state1)
            return out, state.at[l, :rows].set(state1), tail1

    def kernel(state, tail0, interpret=False):
        out, held, tail1 = step(state_step.Rows(state, l, rows, keep, interpret), tail0)
        return out, held.state, tail1

    with stage("state_scan"):
        tail0 = conv[l, :rows]
    fits = state_step.tiles(*state.shape[2:])
    out, state, tail1 = _choose(kernel, plain, fits, interpret)(state, tail0)
    with stage("state_scan"):
        if keep is not None:
            tail1 = jnp.where(keep[(slice(None), *[None] * (tail0.ndim - 1))], tail0, tail1)
        return out, state, conv.at[l, :rows].set(tail1.astype(conv.dtype))


def prefill_scans_in_kernel(state, tokens: int, mesh=None) -> bool:
    """Whether a prefill program of ``tokens`` rows built in this process
    scans a delta rule's ``state`` ``[L', slots + 1, H, d_k, d_v]`` through
    the kernel of :mod:`ray_tpu.ops.delta_scan` (:func:`state_prefill`'s
    choice, which platform, rows and shapes make:
    :func:`ray_tpu.ops.delta_scan.fits`)."""
    return delta_scan.fits(tokens, *state.shape[2:], mesh)


def state_steps_in_kernel(state, mesh=None) -> bool:
    """Whether a decode program built in this process steps a pool's
    ``state`` ``[L', slots + 1, H, a, b]`` through the kernel of
    :mod:`ray_tpu.ops.state_step` (:func:`state_decode`'s choice, which
    platform and shapes make: :func:`ray_tpu.ops.state_step.fits`)."""
    return state_step.fits(*state.shape[2:], mesh)


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
    interpret: bool = False,  # a delta-rule family's scan kernel in the
    #             Pallas interpreter, whatever the platform and shapes (tests)
):
    """Prefill positions [start, start+T) into the pool; return
    (pool, last_logits [vocab] f32), and a third value, its counters, from
    a family that has some.

    The one prefill program serves both the fresh path (start=0) and the
    prefix-continue path — attention always spans the full gathered row
    under the mask ``col <= start + row`` (the static-shape trade)."""
    mod = family(cfg)
    if not cache(cfg).hooks:
        return mod.paged_prefill(
            params, tokens, length, start, table, pool, cfg,
            block_size=block_size, slot=slot, **({"interpret": True} if interpret else {}),
        )
    B, T = tokens.shape
    W = table.shape[0]
    S = W * block_size
    embed, qkv, finish, final, H, KH, Dh = mod.kv_hooks(cfg, S)
    group = H // KH

    pos = start + jnp.arange(T, dtype=jnp.int32)  # [T]
    x = embed(params, tokens, pos[None])
    with stage("attn_core"):
        cols = jnp.arange(S)
        mask = cols[None, :] <= pos[:, None]  # [T, S]
    scale = 1.0 / (Dh**0.5)

    def body(carry, layer):
        x, pk, pv = carry  # pk/pv: the whole pool, [L, N, KH, block, Dh]
        p, l = layer
        q, k, v = qkv(x, p, pos[None])  # q [1,H,T,Dh], k/v [1,KH,T,Dh]
        with stage("attn_proj"):
            kt = k[0].transpose(1, 0, 2)  # [T, KH, Dh]
            vt = v[0].transpose(1, 0, 2)
        # This request's row (transient): [W,KH,block,Dh] -> [KH,S,Dh]
        pk, kd = _write_blocks_read(pk, l, table, start, kt, block_size)
        pv, vd = _write_blocks_read(pv, l, table, start, vt, block_size)
        with stage("attn_core"):
            kd = kd.transpose(1, 0, 2, 3).reshape(KH, S, Dh)
            vd = vd.transpose(1, 0, 2, 3).reshape(KH, S, Dh)
            qg = q[0].reshape(KH, group, T, Dh)
            s = jnp.einsum("kgtd,ksd->kgts", qg, kd).astype(jnp.float32) * scale
            s = jnp.where(mask[None, None], s, -1e30)
            pa = jax.nn.softmax(s, axis=-1).astype(vd.dtype)
            attn = jnp.einsum("kgts,ksd->kgtd", pa, vd).reshape(1, H, T, Dh)
        return (finish(x, attn, p), pk, pv), None

    x, pool = _scan_layers(body, x, params, pool)
    with stage("embed_head"):
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
    refused = cache(cfg).why_not(cfg.family, "paged_verify")
    if refused:
        raise ValueError(refused)
    B, T = tokens.shape
    W = tables.shape[1]
    S = W * block_size
    embed, qkv, finish, final, H, KH, Dh = family(cfg).kv_hooks(cfg, S)
    group = H // KH

    pos2d = positions[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    x = embed(params, tokens, pos2d)  # [B, T, D]
    with stage("pool_write"):
        rows = jnp.arange(B)
        bids = tables[rows[:, None], pos2d // block_size]  # [B, T]
        offs = pos2d % block_size
    with stage("attn_core"):
        cols = jnp.arange(S)
        mask = cols[None, None, :] <= pos2d[:, :, None]  # [B, T, S]
    scale = 1.0 / (Dh**0.5)

    def body(carry, layer):
        x, pk, pv = carry  # pk/pv: the whole pool, [L, N, KH, block, Dh]
        p, l = layer
        q, k, v = qkv(x, p, pos2d)  # q [B,H,T,Dh], k/v [B,KH,T,Dh]
        with stage("attn_proj"):
            kt = k.transpose(0, 2, 1, 3)  # [B, T, KH, Dh]
            vt = v.transpose(0, 2, 1, 3)
        pk, kd = _write_read(pk, l, bids, offs, kt, tables)
        pv, vd = _write_read(pv, l, bids, offs, vt, tables)
        with stage("attn_core"):
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
    if not cache(cfg).hooks:
        return mod.paged_decode(
            params, last_tokens, positions, tables, pool, cfg,
            block_size=block_size, live=live, **({"interpret": True} if interpret else {}),
        )
    B = last_tokens.shape[0]
    W = tables.shape[1]
    S = W * block_size
    embed, qkv, finish, final, H, KH, Dh = mod.kv_hooks(cfg, S)
    group = H // KH
    attend = decode_attention(attention_kind(cfg), block_size, mesh, interpret)

    x = embed(params, last_tokens[:, None], positions[:, None])  # [B,1,D]
    with stage("pool_write"):
        rows = jnp.arange(B)
        bids = tables[rows, positions // block_size]  # [B]
        offs = positions % block_size
    with stage("attn_core"):
        lengths = positions + 1  # the step's own key is attended

    def body(carry, layer):
        x, pk, pv = carry  # pk/pv: the whole pool, [L, N, KH, block, Dh]
        p, l = layer
        q, k, v = qkv(x, p, positions[:, None])  # [B,{H,KH},1,Dh]
        pk = _write(pk, l, bids, offs, k[:, :, 0, :])
        pv = _write(pv, l, bids, offs, v[:, :, 0, :])
        with stage("attn_proj"):
            qg = q[:, :, 0, :].reshape(B, KH, group, Dh)
        with stage("attn_core"):
            attn = attend(qg, pk, pv, l, tables, lengths)
        with stage("attn_proj"):
            attn = attn.reshape(B, H, 1, Dh)
        return (finish(x, attn, p), pk, pv), None

    x, pool = _scan_layers(body, x, params, pool)
    logits = final(params, x[:, 0, :])
    return pool, logits
