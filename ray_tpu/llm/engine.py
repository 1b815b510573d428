"""LLMEngine: slot-based continuous batching over the JAX decode path.

Reference parity: the role vLLM's engine plays under ray.llm
(python/ray/llm/_internal/serve/engines/vllm/vllm_engine.py). Redesigned:

- **Two compiled programs total.** ``prefill`` (one per prompt-length
  bucket) and ``decode_step`` (one). Static shapes everywhere: the decode
  batch is always [max_slots] — idle slots decode garbage that is never
  read. On TPU this trades a few wasted FLOPs for zero recompiles, the
  profitable side of that trade at every batch size.
- **Continuous batching**: a request occupies a cache slot from admission
  until EOS/max_tokens; new requests prefill into freed slots between
  decode steps, so long generations never block short ones behind a
  static batch barrier.
- **A decode step in flight.** Where every active row is greedy the
  program chooses the tokens (argmax over the logits, which stay on the
  device) and ``step()`` launches step N+1, fed by step N's tokens on the
  device, before it reads step N's ``[B]`` int32: the host's round (read,
  books, the pump, admission) runs while the device works. ``max_tokens``
  and ``max_seq`` ends are counts the host knows in time; a stop token is
  learnt a step late, and that row's one extra step is discarded. A
  temperature, a speculative decoder or a replaced
  ``_sample`` make a turn synchronous (logits to the host, nothing in
  flight when ``step()`` returns): read off the input, set by nobody.
- **Every launch numbered.** The prefill and decode programs take the pool
  from the launch before them, so the device runs them in the order the
  host launched them: ``stats["programs_launched"]`` counts them, and the
  flight recorder's ``llm.prefill`` / ``llm.prefill_chunk`` (``seq``) and
  ``llm.decode_step`` (``seq``: the step read, ``next_seq``: the step
  launched ahead, 0 where none) carry their program's number, so that a
  span finds its run in a device trace by counting, whatever the clocks
  say. A step launched and dropped unread keeps its number and has no
  span. The speculative decoder's programs (``llm/spec_decode.py``) are
  not numbered: no traced deployment runs it.
- **Tensor parallelism** = the standard rule table over a ``tp`` mesh axis;
  XLA shards the einsums and inserts ICI collectives — no per-layer manual
  split.
"""

from __future__ import annotations

import dataclasses
import logging
import pickle
import time as _time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.core.config import GLOBAL_CONFIG
from ray_tpu.llm.block_manager import BlockManager, WindowBlocks
from ray_tpu.llm.config import LLMConfig, SamplingParams
from ray_tpu.llm.tokenizer import ByteTokenizer
from ray_tpu.models import latent_moe, paged
from ray_tpu.models.common import stage
from ray_tpu.util import flightrec as _flightrec
from ray_tpu.util import metrics as _metrics
from ray_tpu.util.prefix_digest import BYTE_BOS_SCHEME, chain_digests

# Serving SLO series (recorded per step, not per frame: a decode step is
# milliseconds-scale, so registry locking is negligible here). TTFT =
# admission to first sampled token; ITL = gap between a request's
# consecutive tokens. Tokens-per-second is the rate of the counters.
_TTFT_SECONDS = _metrics.Histogram(
    "raytpu_llm_ttft_seconds",
    "time to first token (request admission to first sample)",
    boundaries=_metrics.LATENCY_BOUNDARIES_S,
)
_ITL_SECONDS = _metrics.Histogram(
    "raytpu_llm_itl_seconds",
    "inter-token latency (gap between consecutive generated tokens)",
    boundaries=_metrics.LATENCY_BOUNDARIES_S,
)
_PROMPT_TOKENS = _metrics.Counter(
    "raytpu_llm_prompt_tokens_total",
    "prompt tokens admitted (prefix-cache reuse included)",
)
_GEN_TOKENS = _metrics.Counter(
    "raytpu_llm_generated_tokens_total",
    "tokens sampled by the decode loop",
)
_PREFILL_CHUNKS = _metrics.Counter(
    "raytpu_llm_prefill_chunks_total",
    "prefill chunks executed on the chunked-prefill path "
    "(prefill_chunk_tokens > 0; one long prompt = several chunks "
    "interleaved with decode steps)",
)
_REQUESTS = _metrics.Counter(
    "raytpu_llm_requests_total", "requests admitted to the engine"
)
# Gauges carry a replica tag: merge is last-wins per (name, tags), so an
# untagged gauge from N engine replicas would show one arbitrary
# replica's value. Histograms/counters sum correctly and stay untagged.
_KV_UTIL = _metrics.Gauge(
    "raytpu_llm_kv_utilization",
    "fraction of KV blocks in use",
    tag_keys=("replica",),
)
_PREFIX_HIT_RATE = _metrics.Gauge(
    "raytpu_llm_prefix_hit_rate",
    "fraction of prefix-pool lookups that reused cached KV",
    tag_keys=("replica",),
)

_log = logging.getLogger(__name__)
# While at least half the slots decode, the decode turns between two prefill
# chunks (LLMEngine._advance_prefills): the chunks' share of the device is
# then at most chunk / (chunk + 3 steps).
_DECODE_TURNS_A_CHUNK = 3
# A prompt that waits while this many chunks go to others counts as one chunk
# shorter when the next chunk is given: no prompt is passed over for ever.
_CHUNKS_PASSED_A_CHUNK = 32
_replica_tags_cache: dict | None = None


def _replica_tags() -> dict:
    """Engine-identity gauge tags: the hosting actor's truncated id
    (bounded by live replicas; series vanish with the process's
    snapshot), or "local" outside an actor (tests, batch inference)."""
    global _replica_tags_cache
    if _replica_tags_cache is None:
        try:
            from ray_tpu.core import api as core_api

            rid = core_api.get_runtime_context().actor_id or ""
        except Exception:  # raylint: disable=RL006 -- runtime-context probe outside an actor; replica tag falls back to 'local'
            rid = ""
        _replica_tags_cache = {"replica": rid[:12] or "local"}
    return _replica_tags_cache


def _validate_block_multiple(name: str, value: int, block_size: int) -> None:
    """Shared config check for every token-granularity knob that must
    align with the paged-KV block size (pooled prefixes are shared, and
    prefill chunks written, at block granularity)."""
    if value % block_size:
        raise ValueError(
            f"{name} ({value}) must be a multiple of kv_block_size "
            f"({block_size}): pooled prefixes are shared and prefill "
            f"chunks written at block granularity"
        )


@dataclasses.dataclass
class _Request:
    request_id: str
    prompt: list
    max_tokens: int
    temperature: float
    stop_token: Optional[int]
    generated: list = dataclasses.field(default_factory=list)
    slot: int = -1
    finished: bool = False
    blocks: list = dataclasses.field(default_factory=list)
    # Chunked prefill: the request holds a slot but is still prefilling
    # its prompt one chunk per step; pf_next is the next absolute prompt
    # position to prefill. No token samples until pf_next reaches the
    # prompt length.
    prefilling: bool = False
    pf_next: int = 0
    pf_passed: int = 0  # chunks that went to other prompts while this one waited
    # Admission failure surfaced via pop_finished (an impossible
    # reservation must fail the REQUEST, not wedge the engine loop).
    error: Optional[str] = None
    # Disaggregated serving: prefill_only requests finish at their first
    # sampled token and carry the exported KV descriptor out through
    # ``handoff_out``; handoff-admitted requests carry the INBOUND
    # descriptor in ``handoff`` until admission pulls (or falls back).
    prefill_only: bool = False
    handoff: Optional[dict] = None
    handoff_out: Optional[dict] = None
    # Speculative decoding: the draft model prefilled this request's
    # prompt, so the slot may join spec steps.
    spec_ready: bool = False
    # Telemetry anchors: admission wall-clock and the previous token's
    # timestamp (TTFT / inter-token latency).
    t_admit: float = 0.0
    t_last_token: float = 0.0
    # Flight-recorder anchors (monotonic): when the request was handed
    # over (llm.queue starts here), and the prefill whose logits have not
    # reached the host yet, as (phase, start, extra): the span ends at
    # the read-back of those logits, where the host waits anyway.
    t_queued: float = 0.0
    pf_open: Optional[tuple] = None
    # A chunk that was not the prompt's last, of a family whose prefill
    # packs counters behind its logits: (its output, still on the device,
    # when its launch returned). Its span is recorded, with the counters,
    # when the request's next chunk is due: the chunk has run by then.
    pf_late: Optional[tuple] = None


@dataclasses.dataclass
class _DecodeStep:
    """A decode program that has been launched and whose tokens the host
    has not read. ``rows`` are the requests whose rows were live in it, in
    the slots they hold; one of them that has finished since (on a stop
    token, learnt from the step before) has had its row computed for
    nothing: it is skipped when the step is read."""

    rows: list
    at: np.ndarray  # the rows' write positions, for the span's fields
    logits: jax.Array  # [B, V]: copied to the host by a synchronous turn only
    small: jax.Array  # int32 [B (+ counters)]: argmax tokens, the programs' counters
    t_launch: float  # monotonic, before its operand was built
    seq: int  # its launch's number: stats["programs_launched"] then


class LLMEngine:
    def __init__(self, config: LLMConfig, tokenizer=None):
        self.config = config
        if config.kv_block_size <= 0:
            raise ValueError(
                f"kv_block_size ({config.kv_block_size}) must be a positive "
                "block size: the engine has one cache, the block pool, for "
                "every family"
            )
        self.tokenizer = tokenizer or ByteTokenizer()
        cfg = config.build_model_config()
        if cfg.vocab_size < self.tokenizer.vocab_size:
            raise ValueError("model vocab smaller than tokenizer vocab")
        self.model_config = cfg
        self._model = paged.family(cfg)
        # What the family keeps for a request (models/paged.py, "What a pool
        # is made of"), read once: a state per slot (resets at position 0,
        # counted), and the table kinds. Every family but one has one kind,
        # which keeps everything; a second kind keeps a window, and the slot
        # a second table (below).
        self._cache = paged.cache(cfg)
        if config.spec_decode_tokens > 0:
            self._refuse("spec_decode_tokens > 0", "speculative verification")
        if config.tensor_parallelism > 1:
            self._refuse("tensor_parallelism > 1", "tensor parallelism")
        kinds = self._cache.retention
        assert kinds[0] is None and len(kinds) <= 2, kinds
        devices = jax.devices()
        tp = config.tensor_parallelism
        if tp > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ray_tpu.parallel import (
                DEFAULT_RULES,
                MeshSpec,
                make_mesh,
                shardings_from_logical,
            )

            self.mesh = make_mesh(MeshSpec(tp=tp), devices[:tp])
            shardings = shardings_from_logical(
                self._model.param_logical_specs(cfg),
                DEFAULT_RULES,
                self.mesh
            )
            self._replicated = NamedSharding(self.mesh, P())
        else:
            self.mesh = None
            shardings = None

        if config.weights_path:
            with open(config.weights_path, "rb") as f:
                params = jax.tree.map(jnp.asarray, pickle.load(f))
        else:
            params = self._model.init_params(
                jax.random.key(config.seed), cfg
            )
        if shardings is not None:
            params = jax.device_put(params, shardings)
        self.params = params

        B, S = config.max_slots, config.max_seq
        bs = config.kv_block_size
        if S % bs:
            raise ValueError("max_seq must be a multiple of kv_block_size")
        if config.enable_prefix_caching:
            _validate_block_multiple("prefix_chunk", config.prefix_chunk, bs)
        if config.prefill_chunk_tokens:
            _validate_block_multiple(
                "prefill_chunk_tokens", config.prefill_chunk_tokens, bs
            )
        # A prefill program's rows are whole blocks from a block's start, and
        # it writes them a block at a time (paged._write_blocks).
        for bucket in config.prefill_buckets:
            _validate_block_multiple("prefill_buckets", bucket, bs)
        self._block_size = bs
        self._table_width = S // bs
        n = config.num_kv_blocks or max(
            (B * self._table_width) // 2, self._table_width + 1
        ) + 1  # +1: block 0 is scratch
        self.block_mgr = BlockManager(n)
        # A slot's tables, a layer kind after another in one row. The blocks
        # of a kind that keeps a window are not reserved from num_kv_blocks:
        # WindowBlocks counts what the slots can hold at most (the window and
        # the longest prefill program looking back on it) and hands them out
        # and takes them back launch by launch.
        W = self._table_width
        self.block_tables = np.zeros((B, len(kinds) * W), np.int32)
        self._window: Optional[WindowBlocks] = None
        if len(kinds) > 1:
            self._window_span = config.prefill_chunk_tokens or max(config.prefill_buckets)
            self._window = WindowBlocks(
                kinds[1], paged.window_blocks_a_slot(kinds[1], self._window_span, bs), bs,
                self.block_tables[:, W:],
            )
        # A family with a recurrent state keeps one row of it a slot
        # (and a scratch row) beside the blocks: max_slots sizes it.
        self.pool = paged.init_block_pool(
            cfg, n, bs, B,
            window_blocks=self._window.mgr.num_blocks if self._window else None,
        )

        # Functions with names of their own, not functools.partial: a
        # device trace then lists the programs as jit_paged_prefill /
        # jit_paged_decode and not as jit__unknown(<hash>). The pool is
        # donated: the programs carry it and scatter in place, so
        # the output is the input's buffer and no step copies 2 x
        # [L, N, KH, block, Dh]. Every call rebinds self.pool; the
        # array passed in is deleted and nothing may keep it.
        # One layout a program for every family: every small operand of a
        # call rides in ONE int32 array (``meta``), handed over as numpy
        # (an upload costs the host 0.5-0.6 ms a piece, and at a 13 ms step
        # three more of them were a tenth of the step and most of its
        # run-to-run noise: PERF.md section 6, PR 29), and a family's
        # counters ride behind what the host reads anyway. paged.paged_prefill
        # and paged.paged_decode return ``(pool, logits)`` and, from a family
        # that has counters, a third value (benchmarks/ unpacks two from the
        # hook families'), so both wrappers take ``pool, logits, *counts``.
        def paged_prefill(params, tokens, meta, pool):
            # meta [3 + W a kind]: length, start, slot, the block table(s);
            # the counters behind the logits are unpacked by _take_counters.
            table = meta[3:] if len(kinds) == 1 else meta[3:].reshape(len(kinds), W)
            pool, logits, *counts = paged.paged_prefill(
                params, tokens, meta[0], meta[1], table, pool,
                cfg=cfg, block_size=bs, slot=meta[2],
            )
            with stage("embed_head"):
                return pool, jnp.concatenate(
                    [logits, *(c.reshape(-1).astype(logits.dtype) for c in counts)]
                )

        self._pg_prefill = jax.jit(paged_prefill, donate_argnums=3)

        def paged_decode(params, prev, meta, pool):
            # meta [B, 4 + W a kind], numpy: position, live, the host's
            # token, whether to use it, the block table(s).
            # ``prev`` is the third output of the step launched before this
            # one, still on the device: a row whose last token the host has
            # not seen (it is in that step) takes it from there, so a
            # greedy token never visits the host on its way to the next
            # step. The choice is the program's too: argmax over float32,
            # the first index on ties, as np.argmax; a family's counters
            # ride behind the tokens, in the one small array a turn reads.
            with stage("embed_head"):
                tokens = jnp.where(meta[:, 3] > 0, meta[:, 2], prev[: meta.shape[0]])
            tables = meta[:, 4:] if len(kinds) == 1 else meta[:, 4:].reshape(-1, len(kinds), W)
            pool, logits, *counts = paged.paged_decode(
                params, tokens, meta[:, 0], tables, pool,
                cfg=cfg, block_size=bs, live=meta[:, 1] > 0, mesh=self.mesh,
            )
            with stage("embed_head"):
                behind = [c.reshape(-1).astype(jnp.int32) for c in counts]
                chosen = jnp.argmax(logits.astype(jnp.float32), axis=-1)
                return pool, logits, jnp.concatenate([chosen.astype(jnp.int32), *behind])

        self._pg_decode = jax.jit(paged_decode, donate_argnums=3)
        # The decode step that has been launched and not read (step()),
        # and what a step with none before it is handed as ``prev``.
        self._inflight: Optional[_DecodeStep] = None
        self._no_prev = None
        # The counts of the admitting turn under way (_in_wave), None in
        # a turn that has launched no prefill and given no slot.
        self._wave: Optional[dict] = None
        # Prefix pool: key (chunk-aligned token tuple hash) ->
        # {"blocks": the prefix's block ids, "tokens", "len", "used"}.
        # LRU within max_prefix_cache_tokens.
        self._prefix_pool: dict = {}
        self._prefix_tokens_cached = 0
        self._prefix_clock = 0
        # Routing advertisement: a stable (cross-process) digest of every
        # chunk-multiple prefix the pool currently holds, rebuilt on pool
        # mutation and swapped in atomically — replica report loops read
        # it from another thread while the pump thread mutates the pool.
        self._digest_snapshot: tuple = ()
        self._digest_version = 0
        self.stats = {
            "prefill_tokens": 0,  # tokens that PAID prefill compute
            # The rows those programs computed (the sum of their buckets),
            # and the turns that launched one or gave a request a slot.
            "prefill_tokens_padded": 0,
            # Of those rows, the blocks a tensor a layer that the programs
            # wrote whole (bucket // kv_block_size a launch): a family whose
            # rows in blocks are keys and values per head; no decode step
            # adds to it.
            "prefill_blocks_written": 0,
            "admit_waves": 0,
            "prefill_chunks": 0,  # chunked-prefill pieces executed
            "prefix_hits": 0,
            "prefix_lookups": 0,
            "prefix_tokens_reused": 0,
            "tokens_generated": 0,
            # Disaggregated serving (llm/disagg.py):
            "handoffs_out": 0,  # prefill-only requests exported
            "handoffs_in": 0,  # handoff admissions that pulled KV
            "kv_fallbacks": 0,  # pulls that failed -> local prefill
            # Speculative decoding (llm/spec_decode.py):
            "spec_steps": 0,
            "spec_drafted": 0,
            "spec_accepted": 0,
            # Plain decode steps, by the arm their program was built with
            # (paged.decode_attends_in_place: platform and shapes decide):
            "decode_attn_kernel_steps": 0,  # live blocks read in place
            "decode_attn_gather_steps": 0,  # whole tables gathered
            # Of those, the programs launched before the step before them
            # was read; and the rows computed for a request that had ended
            # on a stop token by then (never appended, streamed or counted).
            "decode_steps_ahead": 0,
            "decode_rows_discarded": 0,
            # Prefill and decode programs launched: the device runs them in
            # this order, so a launch's count is its run's place in a trace.
            "programs_launched": 0,
            # Prompts cut to their last tokens at add_request: those longer
            # than the largest prefill bucket, or, where the engine prefills
            # in chunks, than max_seq holds beside the answer.
            "prompts_truncated": 0,
        }
        self._decode_arm = (
            "decode_attn_kernel_steps"
            if paged.decode_attends_in_place(cfg, bs, mesh=self.mesh)
            else "decode_attn_gather_steps"
        )
        # Programs (prefills and decode steps alike) of a model with routed
        # experts, by the arm their grouped products were built with
        # (ops.moe_gmm.fits: platform and the experts' widths decide).
        self._moe_arm = self._state_arm = None
        in_kernel = latent_moe.grouped_products_in_kernel(params, cfg, self.mesh)
        if in_kernel is not None:
            self.stats["moe_gmm_kernel_steps"] = 0  # each touched expert streamed once
            self.stats["moe_gmm_ragged_steps"] = 0  # jax.lax.ragged_dot
            self._moe_arm = "moe_gmm_kernel_steps" if in_kernel else "moe_gmm_ragged_steps"
        for path, arr in jax.tree_util.tree_flatten_with_path(self.pool)[0]:
            part = "_".join(str(k.key) for k in path)  # bytes of each cache part
            self.stats[f"cache_bytes_{part}"] = int(arr.nbytes)
            if self._cache.kinds:  # ... and as the device lays it out (a minor dimension in whole tiles)
                self.stats[f"cache_bytes_laid_{part}"] = int(arr.on_device_size_in_bytes())
        # What the mathematics needs of the same blocks, a table kind, where
        # the family's record states its kinds: a pool that pads shows as laid
        # bytes over these.
        blocks = (n, *([self._window.mgr.num_blocks] if self._window else []))
        needed = [kind.row_bytes * bs * count for kind, count in zip(self._cache.kinds, blocks)]
        for i, nbytes in enumerate(needed):
            self.stats[f"cache_bytes_needed_kind{i}"] = nbytes
        if needed:  # ... and of all kinds together, whatever else the pool holds beside them
            self.stats["cache_bytes_needed_kv"] = sum(needed)
        if self._cache.slot_state:
            # Prefills that began a sequence and so began from zero state,
            # whatever the slot held.
            self.stats["state_resets"] = 0
            # Decode programs launched, by the arm their state steps were
            # built with (paged.state_steps_in_kernel: platform and the
            # state's tiles decide).
            self.stats["state_kernel_steps"] = 0  # a tile held on the chip: one read, one write
            self.stats["state_plain_steps"] = 0  # plain jax.numpy on the rows
            self._state_arm = (
                "state_kernel_steps"
                if paged.state_steps_in_kernel(self.pool["state"], self.mesh)
                else "state_plain_steps"
            )
        if self._cache.delta_rule:
            # Prefill (or chunk) programs launched, by the arm their
            # delta-rule layers' scan was built with
            # (paged.prefill_scans_in_kernel: platform, the heads' widths and
            # the program's bucket decide).
            self.stats["prefill_scan_kernel_runs"] = 0  # state and chunk held on the chip
            self.stats["prefill_scan_plain_runs"] = 0  # lax.scan over XLA's fusions
        if self._cache.prefill_in_place:
            # Prefill programs launched, by the arm their attention was built
            # with (paged.prefill_attends_in_kernel: platform, the kinds'
            # shapes and the program's bucket decide).
            self.stats["prefill_attn_kernel_chunks"] = 0  # one kernel call a layer over the table
            self.stats["prefill_attn_fold_chunks"] = 0  # runs of einsums, a stretch at a time
        if not self._cache.shares_prefixes:
            # Admissions that would have looked a prefix up and could not (a
            # hit needs the state, or the window layers' blocks, at the
            # prefix's end: neither is kept).
            self.stats["prefix_cache_bypassed"] = 0
        if self._window is not None:
            # Window blocks given back to the free list while their request
            # still ran.
            self.stats["window_blocks_released"] = 0
        # Host-side slot state (numpy: mutated per step)
        self.positions = np.zeros(B, np.int32)  # next write position
        self.last_tokens = np.zeros(B, np.int32)
        self.slot_free = [True] * B
        self.requests: dict[str, _Request] = {}
        self._slot_req: list = [None] * B
        self._rng = np.random.default_rng(config.seed)
        self._turns_since_chunk = 0  # decode turns since a prefill chunk ran
        self._steps = 0
        self._published_tokens = 0  # tokens already inc'd into the counter
        # Rolling TTFT window ((monotonic, seconds) pairs): a ROUTING/
        # overload signal, not telemetry — recorded regardless of the
        # metrics kill switch and read by router_state() advertisements
        # (serve admission watermark "rolling TTFT"). Samples EXPIRE by
        # age as well as by count: an idle engine must stop advertising
        # its last crisis, or a shed level raised on TTFT would latch
        # forever on the frozen window it caused (no admissions -> no new
        # samples). Appends from the pump thread, p95 reads from the
        # report loop: deque ops are atomic, the reader copies.
        from collections import deque

        self._ttft_window: deque = deque(maxlen=64)
        self.TTFT_WINDOW_S = 30.0
        # Disaggregated serving: (uuid, armed_at) of KV exports awaiting a
        # decode-replica pull (TTL-released by the next export).
        self._kv_exports: list = []
        # Speculative decoding: built only when the config asks for it AND
        # the kill switch is not thrown — with self._spec None, step() is
        # byte-identical to the round-12 engine.
        self._spec = None
        if config.spec_decode_tokens > 0 and GLOBAL_CONFIG.spec_decode:
            from ray_tpu.llm.spec_decode import SpecDecoder

            self._spec = SpecDecoder(
                self, config.draft_model_config, config.spec_decode_tokens
            )

    def _refuse(self, option: str, what: str) -> None:
        """What the engine cannot do for the family (``what``: speculative
        verification, tensor parallelism, the disaggregated handoff) is
        refused by name where ``option`` asks for it, at construction or at
        the request, not as a shape error later: the family's record says
        why."""
        why = self._cache.why_not(self.model_config.family, what)
        if why:
            raise ValueError(f"{option}: {why}")

    # -- admission -----------------------------------------------------------
    def add_request(
        self,
        request_id: str,
        prompt: "str | list",
        sampling: SamplingParams | None = None,
        prefill_only: bool = False,
        t_queued: float | None = None,
    ) -> None:
        """Admit a request. ``prefill_only`` (disaggregated serving's
        prefill leg) finishes the request at its first
        sampled token with the prompt KV exported as ``handoff_out``
        instead of joining the decode batch. ``t_queued`` is the
        monotonic time the caller took the request in, where that was
        earlier than this call (the flight recorder's ``llm.queue`` span
        starts there)."""
        if prefill_only:
            self._refuse("prefill_only (the KV export)", "the disaggregated handoff")
        sampling = sampling or SamplingParams()
        ids = (
            self.tokenizer.encode(prompt)
            if isinstance(prompt, str)
            else list(prompt)
        )
        max_prompt = max(self.config.prefill_buckets)
        # In chunks a prompt may be as long as the cache holds beside its
        # answer, where the ladder has a bucket for every chunk of it.
        longest = self.config.max_seq - sampling.max_tokens
        if (
            len(ids) > max_prompt and longest > max_prompt
            and self._chunks_feasible(0, min(len(ids), longest))
        ):
            max_prompt = longest
        if len(ids) > max_prompt:
            if not self.stats["prompts_truncated"]:
                _log.warning(
                    "prompt of %d tokens cut to its last %d: the largest prefill "
                    "bucket, or with prefill_chunk_tokens what max_seq holds beside "
                    "the answer (counted from here on in stats['prompts_truncated'])",
                    len(ids), max_prompt,
                )
            self.stats["prompts_truncated"] += 1
            ids = ids[-max_prompt:]
        stop = (
            sampling.stop_token
            if sampling.stop_token is not None
            else self.tokenizer.eos_id
        )
        self.requests[request_id] = _Request(
            request_id=request_id,
            prompt=ids,
            max_tokens=sampling.max_tokens,
            temperature=sampling.temperature,
            stop_token=stop,
            prefill_only=prefill_only,
            t_admit=_time.perf_counter(),
            t_queued=_time.monotonic() if t_queued is None else t_queued,
        )
        if _metrics.metrics_enabled():
            _REQUESTS.inc(1.0)
            _PROMPT_TOKENS.inc(float(len(ids)))

    def add_handoff_request(
        self,
        request_id: str,
        handoff: dict,
        sampling: SamplingParams | None = None,
        t_queued: float | None = None,
    ) -> None:
        """Admit a disaggregated request from a prefill replica's handoff:
        the prompt KV arrives over the transfer fabric at admission and
        the request joins the decode batch with its first token already
        sampled — this replica never prefills the prompt (unless the pull
        fails, in which case admission falls back to the local, chunked
        when configured, prefill path). Counts neither requests_total nor
        prompt_tokens: the prefill replica already did."""
        self._refuse("a handoff request (the KV import)", "the disaggregated handoff")
        sampling = sampling or SamplingParams()
        stop = (
            sampling.stop_token
            if sampling.stop_token is not None
            else self.tokenizer.eos_id
        )
        ids = list(handoff.get("prompt") or [])
        req = _Request(
            request_id=request_id,
            prompt=ids,
            max_tokens=sampling.max_tokens,
            temperature=sampling.temperature,
            stop_token=stop,
            handoff=dict(handoff),
            t_admit=_time.perf_counter(),
            t_queued=_time.monotonic() if t_queued is None else t_queued,
        )
        self.requests[request_id] = req

    # -- prefix pool ---------------------------------------------------------

    def _aligned_prefix_len(self, prompt_len: int) -> int:
        """Longest chunk-aligned STRICT prefix (>= 1 token must remain to
        prefill, or there are no last-logits to sample from)."""
        chunk = self.config.prefix_chunk
        return ((prompt_len - 1) // chunk) * chunk

    def _chain_hashes(self, prompt: list) -> dict:
        """Rolling per-chunk hash chain (vLLM-style): H_p = hash((H_{p-c},
        chunk)). One O(len) pass serves every candidate length — no
        per-candidate rehash of the whole prefix."""
        chunk = self.config.prefix_chunk
        chain: dict[int, int] = {}
        h = 0
        for p in range(chunk, self._aligned_prefix_len(len(prompt)) + 1, chunk):
            h = hash((h, tuple(prompt[p - chunk : p])))
            chain[p] = h
        return chain

    def _find_prefix(self, prompt: list):
        """Longest pooled prefix of ``prompt``; returns (entry | None).
        Hits are verified against the stored tokens, so a hash collision
        can never serve another prompt's KV. A family with a recurrent
        state, or with layers that keep a window, is never served from the
        pool: a hit would need the state, or the blocks behind the window,
        at the prefix's end."""
        if not (self.config.enable_prefix_caching and self._cache.shares_prefixes):
            return None
        self.stats["prefix_lookups"] += 1
        chain = self._chain_hashes(prompt)
        for p in sorted(chain, reverse=True):
            entry = self._prefix_pool.get((chain[p], p))
            if entry is not None and entry["tokens"] == tuple(prompt[:p]):
                self._prefix_clock += 1
                entry["used"] = self._prefix_clock
                return entry
        return None

    def _insert_prefix(self, prompt: list, blocks: list) -> None:
        """Pool the prompt's longest aligned prefix: take a reference on
        the request's first P/block blocks — sharing, not copying."""
        if not (self.config.enable_prefix_caching and self._cache.shares_prefixes):
            return
        p = self._aligned_prefix_len(len(prompt))
        if p < self.config.prefix_chunk or p > self.config.max_prefix_cache_tokens:
            return
        chain = self._chain_hashes(prompt)
        key = (chain[p], p)
        self._prefix_clock += 1
        existing = self._prefix_pool.get(key)
        if existing is not None and existing["tokens"] == tuple(prompt[:p]):
            existing["used"] = self._prefix_clock
            return
        while (
            self._prefix_pool
            and self._prefix_tokens_cached + p
            > self.config.max_prefix_cache_tokens
        ):
            self._evict_one_prefix()
        shared = list(blocks[: p // self._block_size])
        self.block_mgr.incref(shared)
        self._prefix_pool[key] = {
            "len": p,
            "used": self._prefix_clock,
            "tokens": tuple(prompt[:p]),
            "blocks": shared,
        }
        self._prefix_tokens_cached += p
        self._refresh_digest_snapshot()

    def _admit_waiting(self) -> list:
        """Admit waiting requests into free slots; returns requests that
        finished DURING admission (max_tokens=1 / stop token at prefill) —
        step() must surface these too, or their callers never learn.

        FIFO: the first request that cannot be admitted (no slot, or not
        enough free KV blocks) stops the wave, so a big
        request cannot be starved by small ones slipping past it."""
        admit_finished: list = []
        waiting = [
            r for r in self.requests.values() if r.slot < 0 and not r.finished
        ]
        fr = _flightrec.on()
        for req in waiting:
            try:
                slot = self.slot_free.index(True)
            except ValueError:
                break
            if fr:  # where this attempt starts, and the counters then
                mark = (
                    _time.monotonic(), self.stats["prefill_tokens"],
                    self.stats["prefix_tokens_reused"],
                )
            if req.handoff is not None:
                verdict = self._admit_handoff(req, slot)
                if verdict == "wait":
                    break
                if verdict == "done":
                    if fr and req.error is None:
                        self._rec_admitted(req, *mark)
                    if req.finished:
                        admit_finished.append(req)
                    continue
                # "fallback": the pull failed and the handoff is cleared —
                # the local admission paths below (chunked prefill
                # included) take over, token-identical under greedy.
            logits = self._admit_paged(req, slot)
            if req.finished:
                # Permanently unadmittable (oversized reservation): it
                # finished with an error; the wave continues — an
                # impossible request must not starve admittable ones.
                admit_finished.append(req)
                continue
            if req.prefilling:
                # Chunked prefill took the slot but defers its first
                # sample to _advance_prefills; keep admitting.
                if fr:
                    self._rec_admitted(req, *mark)
                continue
            if logits is None:
                break
            T = len(req.prompt)
            logits_np = self._take_counters(np.asarray(logits), req)  # raylint: disable=RL101 -- admission sampling: first token sampled host-side from the last-logits readback
            self._close_prefill_span(req)
            tok = self._sample(logits_np, req)
            if fr:
                self._rec_admitted(req, *mark)
            self._take_slot(req, slot)
            if req.prefill_only:
                # Disaggregated prefill leg: export the prompt KV and
                # finish here — the decode tier takes it from the handoff.
                self._finish_prefill_only(req, tok)
                admit_finished.append(req)
                continue
            req.generated.append(tok)
            self.stats["tokens_generated"] += 1
            req.t_last_token = _time.perf_counter()
            self._ttft_window.append(
                (_time.monotonic(), req.t_last_token - req.t_admit)
            )
            if _metrics.metrics_enabled():
                _TTFT_SECONDS.observe(req.t_last_token - req.t_admit)
            self._rec_first_token(req)
            self.positions[slot] = T
            self.last_tokens[slot] = tok
            if self._spec is not None:
                req.spec_ready = self._spec.prefill_draft(req)
            self._maybe_finish(req)
            if req.finished:
                admit_finished.append(req)
        if self._wave is not None:
            self._wave["waiting"] = len(waiting)
            self._wave["left"] = sum(
                r.slot < 0 and not r.finished for r in waiting
            )
        return admit_finished

    def _in_wave(self) -> dict:
        """The counts of the admitting turn under way, which ``step()``
        records as ``llm.admit_wave``; begun by the turn's first prefill
        launch or slot taken. Until then no row has joined or left the
        decoding ones, so ``rows_stalled`` is counted here as it stood at
        the turn's entry."""
        if self._wave is None:
            self.stats["admit_waves"] += 1
            self._wave = {
                "wave": self.stats["admit_waves"], "waiting": 0, "left": 0,
                "admitted": 0, "prefills": 0, "tokens": 0, "padded": 0,
                "reused": 0,
                "rows_stalled": sum(
                    r is not None and not r.prefilling for r in self._slot_req
                ),
            }
        return self._wave

    def _take_slot(self, req: _Request, slot: int) -> None:
        """``req`` holds ``slot`` from here on: one admission of the wave."""
        self._in_wave()["admitted"] += 1
        req.slot = slot
        self.slot_free[slot] = False
        self._slot_req[slot] = req

    @staticmethod
    def _rec_first_token(req: _Request) -> None:
        """Flight-recorder TTFT phase: admission -> first sampled token,
        recorded as one interval ending now (mono clock; t_admit is a
        perf_counter anchor so the duration, not its wall start, is the
        trusted quantity)."""
        if not _flightrec.on():
            return
        ttft = max(0.0, req.t_last_token - req.t_admit)
        _flightrec.record(
            "llm", "llm.first_token",
            t=_time.monotonic() - ttft, dur_s=ttft, rid=req.request_id,
        )

    def _rec_admitted(
        self, req: _Request, t_adm: float, paid: int, reused: int
    ) -> None:
        """The two flight-recorder spans of one admission, recorded once
        it has gone through: ``llm.queue`` from the hand-over of the
        request to the start of the attempt that admitted it (the step in
        flight, earlier prefills, a slot and blocks), and ``llm.admit``
        from there to now: its first token sampled (reservation, prefix
        lookup, prefill, read-back, the first sample), or, for a chunked
        prefill or a handoff, its slot taken. ``tokens`` and ``reused``
        are what this admission added to the engine's counters of prompt
        tokens prefilled and taken from the prefix pool, ``wave`` the
        ``llm.admit_wave`` it belongs to (0: a handoff that had ended at
        its prefill takes no slot and makes no wave)."""
        _flightrec.record(
            "llm", "llm.queue", t=req.t_queued,
            dur_s=t_adm - req.t_queued, rid=req.request_id,
        )
        _flightrec.record(
            "llm", "llm.admit", t=t_adm,
            dur_s=_time.monotonic() - t_adm, rid=req.request_id,
            tokens=self.stats["prefill_tokens"] - paid,
            reused=self.stats["prefix_tokens_reused"] - reused,
            wave=self._wave["wave"] if self._wave is not None else 0,
        )

    def _open_prefill_span(self, req: _Request, phase: str, t_pf: float, **extra):
        """Note on ``req`` the prefill that ``_run_prefill`` has just
        dispatched, with its launch's number and its wave's; its span is
        recorded by ``_close_prefill_span``."""
        if _flightrec.on():
            extra["wave"] = self._wave["wave"]
            extra["seq"] = self.stats["programs_launched"]
            if self._cache.slot_state:  # began from the slot's state, or from zero
                extra["state_carried"] = int(extra.get("start", 0) > 0)
            req.pf_open = (phase, t_pf, extra)

    @staticmethod
    def _close_prefill_span(req: _Request) -> None:
        """End the prefill span that the dispatch left open on ``req``.
        Called where that prefill's logits have just been read back, so
        the span holds the device's work and not only the launch; a chunk
        whose logits nobody reads is closed right after its dispatch."""
        if req.pf_open is None:
            return
        phase, t_pf, extra = req.pf_open
        req.pf_open = None
        _flightrec.record(
            "llm", phase, t=t_pf, dur_s=_time.monotonic() - t_pf,
            rid=req.request_id, **extra,
        )

    def _close_late_span(self, req: _Request) -> None:
        """Record the span of ``req``'s chunk before this one (``pf_late``):
        the launch, as for any chunk whose logits nobody samples from, with
        the counters its program packed behind them."""
        if req.pf_late is None:
            return
        out, t_end = req.pf_late
        req.pf_late = None
        self._take_counters(np.asarray(out), req)  # raylint: disable=RL101 -- a chunk launched a turn ago: its counters, for its span
        phase, t_pf, extra = req.pf_open
        req.pf_open = None
        _flightrec.record(
            "llm", phase, t=t_pf, dur_s=t_end - t_pf, rid=req.request_id, **extra,
        )

    def _take_counters(self, out: np.ndarray, req: _Request) -> np.ndarray:
        """The logits of a prefill whose read-back is ``out``. What a
        family's program packed behind them (__init__) is its counters: they
        go onto the prefill span still open on ``req``."""
        V = self.model_config.vocab_size
        # (A family of kv_hooks has no fields of its own; one without
        # counters still says what its run stepped.)
        if not self._cache.hooks and req.pf_open is not None:
            phase, t_pf, extra = req.pf_open
            extra = {
                **extra,
                **self._model.span_fields(
                    self.model_config, out[V:], extra["tokens"], 1
                ),
            }
            req.pf_open = (phase, t_pf, extra)
        return out[:V]

    def _run_prefill(self, toks, n: int, start: int, row, slot: int):
        """Dispatch one paged prefill of ``n`` tokens from position
        ``start`` into ``slot``; rebinds the donated pool and returns the
        program's second output, still on the device. Every prefill
        program goes through here, so here they are counted: the tokens
        given and the rows computed (``toks``' width, the bucket), for
        the engine and for the turn's wave, and the launch's number."""
        if self._window is not None:
            self._advance_window(slot, start, start + n)
            row = self.block_tables[slot]  # with the window kind's entries
        if self._cache.slot_state and start == 0:
            # begins from zero state, whatever the slot held
            self.stats["state_resets"] += 1
        meta = np.concatenate([[n, start, slot], row]).astype(np.int32)
        if self._moe_arm:
            self.stats[self._moe_arm] += 1
        bucket = toks.shape[1]
        if self._cache.delta_rule:
            in_kernel = paged.prefill_scans_in_kernel(self.pool["state"], bucket, self.mesh)
            self.stats[f"prefill_scan_{'kernel' if in_kernel else 'plain'}_runs"] += 1
        if self._cache.prefill_in_place:
            in_kernel = paged.prefill_attends_in_kernel(
                self.model_config, self._block_size, bucket, mesh=self.mesh
            )
            self.stats[f"prefill_attn_{'kernel' if in_kernel else 'fold'}_chunks"] += 1
        wave = self._in_wave()
        if not wave["prefills"] and self._inflight is not None:
            # The wave's first launch queues behind what is left of the
            # decode step in flight.
            wave["inflight_age_ms"] = (
                _time.monotonic() - self._inflight.t_launch
            ) * 1e3
        wave["prefills"] += 1
        wave["tokens"] += n
        wave["padded"] += bucket
        self.stats["prefill_tokens"] += n
        self.stats["prefill_tokens_padded"] += bucket
        if self._cache.per_head:
            self.stats["prefill_blocks_written"] += bucket // self._block_size
        self.stats["programs_launched"] += 1
        self.pool, out = self._pg_prefill(self.params, toks, meta, self.pool)
        return out

    def _advance_window(self, slot: int, first_query: int, upto: int) -> None:
        """Before a launch that writes ``slot``'s positions up to ``upto`` and
        whose first query stands at ``first_query``: the window kind's blocks
        behind the window go back, those the writes need are taken
        (``WindowBlocks.advance``), and the slot's table follows."""
        self._window.advance(slot, first_query, upto)
        self.stats["window_blocks_released"] = self._window.released

    def _admit_handoff(self, req: _Request, slot: int) -> str:
        """Admit a disaggregated handoff: reserve blocks, pull the shipped
        KV into them, join the decode batch with the first token already
        sampled — this replica never prefills the prompt. Returns "done"
        (admitted, or finished without a slot), "wait" (no blocks free —
        the FIFO wave stops), or "fallback" (the pull failed: handoff
        cleared, the caller runs local admission)."""
        from ray_tpu.llm import disagg

        h = req.handoff
        if h.get("finished"):
            # Stop token / max_tokens hit at prefill: the shipped first
            # token IS the whole response; no KV, no slot.
            req.handoff = None
            req.generated.append(int(h["first_token"]))
            self.stats["tokens_generated"] += 1
            req.t_last_token = _time.perf_counter()
            req.finished = True
            return "done"
        if (
            not h.get("kv")
            or int(h.get("block_size") or 0) != self._block_size
        ):
            # Malformed or foreign block geometry: local prefill.
            req.handoff = None
            self.stats["kv_fallbacks"] += 1
            return "fallback"
        T = len(req.prompt)
        bs = self._block_size
        total = min(T + req.max_tokens, self.config.max_seq)
        nb_total = -(-total // bs)
        nb_kv = int(h["nblocks"])
        if nb_total > self.block_mgr.num_blocks - 1:
            req.error = (
                f"request {req.request_id} needs {nb_total} KV blocks but "
                f"the pool only has {self.block_mgr.num_blocks - 1}; raise "
                f"num_kv_blocks or lower max_tokens"
            )
            req.finished = True
            return "done"
        if not self.block_mgr.can_alloc(nb_total):
            self._evict_prefixes_until(nb_total)
            if not self.block_mgr.can_alloc(nb_total):
                return "wait"
        table = self.block_mgr.alloc(nb_total)
        try:
            kv = disagg.pull_kv(h, req.request_id)
            pk = self.pool["k"]
            if (
                kv.shape[0] != 2
                or kv.shape[1] != pk.shape[0]
                or kv.shape[2] < nb_kv
                or kv.shape[3:] != pk.shape[2:]
            ):
                raise ValueError(
                    f"handoff KV shape {kv.shape} does not fit pool "
                    f"{pk.shape}"
                )
        except Exception:  # raylint: disable=RL006 -- ANY pull failure (sever, dead peer, bad shape) takes the counted local-prefill fallback
            self.block_mgr.decref(table)
            req.handoff = None
            self.stats["kv_fallbacks"] += 1
            return "fallback"
        self.pool = disagg.scatter_into_pool(self, kv, table[:nb_kv])
        req.blocks = table
        row = np.zeros(self.block_tables.shape[1], np.int32)
        row[: len(table)] = table
        self.block_tables[slot] = row
        self._take_slot(req, slot)
        tok = int(h["first_token"])
        req.handoff = None
        req.generated.append(tok)
        self.stats["tokens_generated"] += 1
        self.stats["handoffs_in"] += 1
        # No TTFT here: the first token was produced (and its TTFT
        # observed) on the prefill replica; this clock anchors ITL only.
        req.t_last_token = _time.perf_counter()
        self.positions[slot] = T
        self.last_tokens[slot] = tok
        if self._spec is not None:
            req.spec_ready = self._spec.prefill_draft(req)
        self._maybe_finish(req)
        return "done"

    def _finish_prefill_only(self, req: _Request, tok: int) -> None:
        """Finish a prefill-only request at its first sampled token:
        record the token, export the prompt KV for the decode tier (while
        the blocks are still held — the gather copies), then release the
        slot. TTFT is observed HERE: the prefill replica produced the
        first token."""
        from ray_tpu.llm import disagg

        req.generated.append(tok)
        self.stats["tokens_generated"] += 1
        req.t_last_token = _time.perf_counter()
        self._ttft_window.append(
            (_time.monotonic(), req.t_last_token - req.t_admit)
        )
        if _metrics.metrics_enabled():
            _TTFT_SECONDS.observe(req.t_last_token - req.t_admit)
        self._rec_first_token(req)
        done = req.max_tokens <= 1 or tok == req.stop_token
        req.handoff_out = disagg.export_kv(self, req, tok, finished=done)
        self.stats["handoffs_out"] += 1
        req.finished = True
        self._release_slot(req)

    def _admit_paged(self, req: _Request, slot: int):
        """Reserve blocks, point the slot's table at them (sharing any
        pooled prefix blocks), prefill the suffix. Returns last-logits, or
        None when the pool can't cover the reservation right now.

        Admission reserves ceil(min(T+max_tokens, max_seq)/block) blocks
        up front, so a running request can never hit pool exhaustion
        mid-decode — the no-preemption counterpart of vLLM's watermark."""
        T = len(req.prompt)
        bs = self._block_size
        # Prefill-only requests (disagg) never decode here: reserve for
        # the prompt + the one sampled token, not the decode budget.
        mt = 1 if req.prefill_only else req.max_tokens
        total = min(T + mt, self.config.max_seq)
        entry = self._find_prefix(req.prompt)
        P = 0
        if entry is not None:
            P = entry["len"]
            rem = T - P
            bucket = next(
                (
                    b
                    for b in self.config.prefill_buckets
                    if b >= rem and P + b <= self.config.max_seq
                ),
                None,
            )
            if bucket is None:
                entry, P = None, 0
        if entry is None:
            rem = T
            bucket = next(
                (b for b in self.config.prefill_buckets if b >= T),
                self.config.prefill_buckets[-1],
            )
        nb_total = -(-total // bs)
        need = max(nb_total - P // bs, 0)
        if nb_total > self.block_mgr.num_blocks - 1:
            # The FULL table (shared prefix blocks included — they must be
            # live simultaneously) can never fit the pool: checking only
            # the new-block count would let a prefix-sharing request slip
            # past and wait forever on an impossible reservation.
            # A reservation no pool state can ever satisfy: finish THIS
            # request with an error (surfaced via pop_finished). Raising
            # here would re-raise from every subsequent step() and wedge
            # admission for all other requests (ADVICE round 5).
            req.error = (
                f"request {req.request_id} needs {nb_total} KV blocks but "
                f"the pool only has {self.block_mgr.num_blocks - 1}; raise "
                f"num_kv_blocks or lower max_tokens"
            )
            req.finished = True
            return None
        if (
            self._window is not None and rem > self._window_span
            and not self._chunks_feasible(P, T)
        ):
            # One program over the whole prompt would look back on more
            # window blocks than a slot is counted to hold.
            req.error = (
                f"request {req.request_id}: a prompt of {T} tokens must prefill in "
                f"chunks of at most {self._window_span} over window layers, and no "
                f"ladder of prefill_buckets under max_seq holds its chunks"
            )
            req.finished = True
            return None
        if not self.block_mgr.can_alloc(need):
            # Under allocation pressure the prefix pool must give way:
            # its pinned refs can otherwise hold enough blocks that a
            # max-length request is unadmittable FOREVER (the pool only
            # self-evicts on its token budget). LRU-evict entries — the
            # one this request is about to share is kept — until the
            # reservation fits or the pool is dry (vLLM frees cached
            # blocks on demand the same way).
            self._evict_prefixes_until(need, keep=entry)
            if not self.block_mgr.can_alloc(need):
                return None
        shared: list = []
        if entry is not None:
            shared = list(entry["blocks"])
            self.block_mgr.incref(shared)
        table = shared + self.block_mgr.alloc(need)
        req.blocks = table
        row = np.zeros(self.block_tables.shape[1], np.int32)
        row[: len(table)] = table
        self.block_tables[slot] = row
        if entry is not None:
            self.stats["prefix_hits"] += 1
            self.stats["prefix_tokens_reused"] += P
            self._in_wave()["reused"] += P  # a slot or a launch follows
        if self.config.enable_prefix_caching and not self._cache.shares_prefixes:
            self.stats["prefix_cache_bypassed"] += 1  # once an admission
        if self._chunks_feasible(P, T):
            self._begin_chunked_prefill(req, slot, P)
            return None
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :rem] = req.prompt[P:]
        t_pf = _time.monotonic()
        logits = self._run_prefill(toks, rem, P, row, slot)
        self._open_prefill_span(
            req, "llm.prefill", t_pf, tokens=rem, reused=P, bucket=bucket
        )
        self._insert_prefix(req.prompt, table)
        return logits

    def _evict_one_prefix(self, keep=None) -> bool:
        """Drop the LRU prefix-pool entry (skipping ``keep``), returning
        its tokens to the budget and its block refs to the pool. THE one
        copy of the eviction bookkeeping — both the insert-time token
        budget and allocation-pressure eviction go through it."""
        victims = [k for k, e in self._prefix_pool.items() if e is not keep]
        if not victims:
            return False
        victim = min(victims, key=lambda k: self._prefix_pool[k]["used"])
        evicted = self._prefix_pool.pop(victim)
        self._prefix_tokens_cached -= evicted["len"]
        self.block_mgr.decref(evicted["blocks"])
        # Digest refresh is the CALLERS' duty, once per eviction wave —
        # a per-eviction rebuild would rehash the whole surviving pool
        # N times in an eviction storm (insert budget loop,
        # _evict_prefixes_until).
        return True

    def _evict_prefixes_until(self, need: int, keep=None) -> None:
        """LRU-evict prefix-pool entries until ``need`` blocks are
        allocatable or nothing evictable remains. Entries whose blocks are
        still shared by running requests free nothing when dropped — the
        loop keeps going past them."""
        evicted = False
        while not self.block_mgr.can_alloc(need):
            if not self._evict_one_prefix(keep=keep):
                break
            evicted = True
        if evicted:
            self._refresh_digest_snapshot()

    # -- chunked prefill -----------------------------------------------------
    # A long prompt's suffix prefills in prefill_chunk_tokens-sized pieces,
    # one chunk per engine step, interleaved with decode steps for the
    # slots already generating — so one long prompt bounds in-flight
    # streams' ITL instead of stalling a whole slot-batch for its full
    # prefill. Invariant while a slot is prefilling: positions[slot] ==
    # pf_next (the next chunk's start), so the fixed-shape decode
    # program's garbage write for that slot lands exactly where the next
    # chunk (or, after the final chunk, the first real decode) overwrites
    # it — in the request's OWN blocks, never in shared prefix blocks
    # (pf_next > P always).

    def _chunk_bucket(self, start: int, clen: int):
        """Smallest prefill bucket that holds a ``clen``-token chunk at
        ``start`` WITHOUT reaching past max_seq; None when none fits.
        A position past max_seq clamps to the LAST block-table entry —
        which, for a full-width table (T + max_tokens >= max_seq), is the
        request's own last REAL block, not the scratch block, and the
        padded garbage rows would overwrite real prompt KV."""
        for b in self.config.prefill_buckets:
            if b >= clen and start + b <= self.config.max_seq:
                return b
        return None

    def _chunks_feasible(self, start: int, T: int) -> bool:
        """True when the [start, T) suffix should prefill chunked: the
        knob is on, the suffix is longer than one chunk, and EVERY chunk
        has a fitting bucket (checked up front — a mid-prefill fallback
        would strand a half-filled slot)."""
        chunk = self.config.prefill_chunk_tokens
        if chunk <= 0 or T - start <= chunk:
            return False
        s = start
        while s < T:
            clen = min(chunk, T - s)
            if self._chunk_bucket(s, clen) is None:
                return False
            s += clen
        return True

    def _begin_chunked_prefill(self, req: _Request, slot: int, start: int):
        """Take the slot (blocks/table already reserved); ALL chunk work
        happens in _advance_prefills under its per-step budget — an
        admission wave of long prompts must not burst N first-chunks
        into one step."""
        self._take_slot(req, slot)
        req.prefilling = True
        req.pf_next = start
        self.positions[slot] = start
        self.last_tokens[slot] = 0

    def _prefill_one_chunk(self, req: _Request):
        """Prefill the next chunk of ``req``'s prompt; returns the chunk's
        last-logits (only the final chunk's are ever sampled)."""
        self._close_late_span(req)
        T = len(req.prompt)
        start = req.pf_next
        clen = min(self.config.prefill_chunk_tokens, T - start)
        bucket = self._chunk_bucket(start, clen)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :clen] = req.prompt[start : start + clen]
        t_pf = _time.monotonic()
        logits = self._run_prefill(
            toks, clen, start, self.block_tables[req.slot], req.slot
        )
        self.stats["prefill_chunks"] += 1
        if _metrics.metrics_enabled():
            _PREFILL_CHUNKS.inc(1.0)
        self._open_prefill_span(
            req, "llm.prefill_chunk", t_pf,
            tokens=clen, start=start, bucket=bucket,
        )
        req.pf_next = start + clen
        self.positions[req.slot] = req.pf_next
        return logits

    def _advance_prefills(self) -> list:
        """ONE chunk, for ONE prefilling slot, per step at most: the
        per-step prefill budget is prefill_chunk_tokens TOTAL, so a
        wave of long prompts serializes its prefill across steps instead
        of collectively stalling the decode batch (the token-budget rule
        of Sarathi-style chunked prefill).

        **Whose chunk**: the prompt with the fewest tokens left (the one
        that came first among equals), so a prompt that has begun is
        finished before another begins and a short prompt's first token
        does not wait behind a long one's: with k prompts waiting
        together the slots decode after 1, 2, ... k prompts' worth of
        chunks and not all after k. Every chunk that goes to another
        prompt takes 1 / ``_CHUNKS_PASSED_A_CHUNK`` of a chunk off a
        waiting prompt's count, so a long prompt is passed over by a
        bounded number of chunks however many short ones keep arriving.

        **How often**: while at least half the slots are decoding, a
        chunk follows ``_DECODE_TURNS_A_CHUNK`` turns that ran none, so
        the streams that are decoding keep most of their rate through a
        wave of long prompts (a chunk of 2,048 lasts several decode
        steps) and the chunks take a bounded share of the device; with
        fewer rows decoding there is little to protect and every turn
        runs a chunk, which fills the batch soonest.

        A slot whose final chunk lands samples its first token and joins
        the decode batch. Returns requests that finished here
        (max_tokens=1 / stop at prefill)."""
        pending = [r for r in self._slot_req if r is not None and r.prefilling]
        if not pending:
            return []
        decoding = sum(r is not None for r in self._slot_req) - len(pending)
        if (
            2 * decoding >= len(self._slot_req)
            and self._turns_since_chunk < _DECODE_TURNS_A_CHUNK
        ):
            return []
        credit = self.config.prefill_chunk_tokens / _CHUNKS_PASSED_A_CHUNK
        req = min(
            pending,
            key=lambda r: (len(r.prompt) - r.pf_next - credit * r.pf_passed, r.t_admit),
        )
        for r in pending:
            r.pf_passed += r is not req
        self._turns_since_chunk = 0
        logits = self._prefill_one_chunk(req)
        T = len(req.prompt)
        if req.pf_next < T:
            if logits.shape[0] > self.model_config.vocab_size and req.pf_open is not None:
                req.pf_late = (logits, _time.monotonic())  # its counters are read later
            else:
                self._close_prefill_span(req)  # logits never read: the launch
            return []
        req.prefilling = False
        logits_np = self._take_counters(np.asarray(logits), req)  # raylint: disable=RL101 -- final-chunk sampling: first token sampled host-side from the chunk's last-logits
        self._close_prefill_span(req)
        tok = self._sample(logits_np, req)
        self._insert_prefix(req.prompt, req.blocks)
        if req.prefill_only:
            # Disaggregated prefill leg, chunked variant: export + finish.
            self._finish_prefill_only(req, tok)
            return [req]
        req.generated.append(tok)
        self.stats["tokens_generated"] += 1
        req.t_last_token = _time.perf_counter()
        self._ttft_window.append(
            (_time.monotonic(), req.t_last_token - req.t_admit)
        )
        if _metrics.metrics_enabled():
            _TTFT_SECONDS.observe(req.t_last_token - req.t_admit)
        self._rec_first_token(req)
        self.positions[req.slot] = T
        self.last_tokens[req.slot] = tok
        if self._spec is not None:
            req.spec_ready = self._spec.prefill_draft(req)
        self._maybe_finish(req)
        return [req] if req.finished else []

    def _sample(self, logits: np.ndarray, req: _Request) -> int:
        """The next token of ``req`` from a row of logits on the host: every
        first token (a prefill's logits are read at admission), and every
        row of a synchronous turn. A turn that runs ahead does not come
        here: its rows are greedy and the decode program's own argmax is
        this function's. Replacing it on an engine (the benchmark's output
        check notes the logits and forces the token here) therefore makes
        every turn synchronous: ``_runs_ahead``."""
        if req.temperature <= 0.0:
            return int(np.argmax(logits))
        z = logits / req.temperature
        z = z - z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self._rng.choice(len(p), p=p))

    def _maybe_finish(self, req: _Request) -> None:
        done = (
            len(req.generated) >= req.max_tokens
            or req.generated[-1] == req.stop_token
            or (req.slot >= 0 and self.positions[req.slot] + 1 >= self.config.max_seq)
        )
        if done:
            req.finished = True
            self._release_slot(req)

    def _release_slot(self, req: _Request) -> None:
        """Return a request's slot and block references to the engine.
        Shared prefix blocks stay alive under the pool's own refs; the
        slot's table points at the scratch block so its garbage decode
        writes can never land in a block someone else now owns."""
        if req.slot >= 0:
            self.block_mgr.decref(req.blocks)
            req.blocks = []
            if self._window is not None:
                self._window.release(req.slot)
            self.block_tables[req.slot] = 0
            self.positions[req.slot] = 0
            self.last_tokens[req.slot] = 0
            self.slot_free[req.slot] = True
            self._slot_req[req.slot] = None
            req.slot = -1

    # -- the engine loop ------------------------------------------------------
    def step(self) -> list:
        """Admit, then one decode step for all active slots. Returns the
        requests that finished this turn.

        **What may be in flight when it returns.** Where every active row is
        greedy, a turn launches the NEXT turn's decode program before it
        reads this one's tokens (``_runs_ahead`` says when; ``_decode_turn``
        how), so that the device goes from one program into the next while
        the host keeps its books and comes back through the pump. Then
        ``self.pool`` is the handle that program will fill, ``positions``,
        ``last_tokens`` and ``generated`` hold what the host has READ, one
        token behind the device, and ``block_tables`` has already been
        handed over. Whatever is launched later (a prefill, a handoff's
        scatter, an export's gather) takes the pool from that handle and so
        runs after it on the device: reading or rebinding ``pool`` between
        steps is always safe. Writing ``block_tables`` or ``positions``
        between steps, or expecting the next step to see such a write, is
        for callers whose engine is on the synchronous arm (a replaced
        ``_sample``, a temperature, a speculative decoder): there nothing
        is in flight between steps."""
        instrument = _metrics.metrics_enabled()
        fr = _flightrec.on()
        t_wave = _time.monotonic() if fr else 0.0
        # Prefill chunks of already-admitted long prompts advance BEFORE
        # this step's admissions, so a request admitted this step runs
        # exactly its first chunk — one chunk per request per step.
        chunks = self.stats["prefill_chunks"]
        finished = self._advance_prefills()
        finished += self._admit_waiting()
        if self._wave is not None:
            # An admitting turn: this stretch launched a prefill program
            # or gave a request a slot, and no decode ran meanwhile. One
            # span a wave, with what _in_wave's callers counted.
            wave, self._wave = self._wave, None
            if fr:
                _flightrec.record(
                    "llm", "llm.admit_wave", t=t_wave,
                    dur_s=_time.monotonic() - t_wave, **wave,
                )
        active = [
            r for r in self._slot_req if r is not None and not r.prefilling
        ]
        if active and self._spec is not None and self._spec_eligible(active):
            finished += self._spec.step(active)
        elif active:
            finished += self._decode_turn(active, instrument)
        else:
            # Ran dry: a step in flight has only rows of requests that
            # ended on a stop token, and goes with them.
            self._inflight = None
        if active and self.stats["prefill_chunks"] == chunks:
            self._turns_since_chunk += 1
        self._steps += 1
        if instrument:
            self._publish_metrics()
        return finished

    def _runs_ahead(self, active: list) -> bool:
        """Whether this turn may choose its tokens on the device and launch
        the next step before it reads them; read off the engine's input, set
        by nobody. Every active row is greedy (the program's argmax is then
        the sample: a temperature draws from ``self._rng`` on the host); no
        speculative decoder is built (it reads ``last_tokens`` on the host);
        and ``_sample`` is the engine's own: whoever replaces it wants every
        row's logits, and may rewrite ``block_tables`` and ``pool`` between
        steps. A slot mid-way through a prefill in chunks does not stand in
        the way: it is not live in the step launched ahead, which writes its
        row of garbage at the slot's cursor as every step does, and the
        slot's next chunk is launched after that step and so runs after it
        on the device and overwrites the row."""
        return (
            self._spec is None
            and getattr(self._sample, "__func__", None) is LLMEngine._sample
            and all(r.temperature <= 0.0 for r in active)
        )

    def _ends_by_count(self, req: _Request) -> bool:
        """Whether ``req``, a row of the step in flight, ends at that step's
        token whatever it is: ``max_tokens`` and ``max_seq`` are counts (what
        ``_maybe_finish`` will find once the token is appended)."""
        return (
            len(req.generated) + 1 >= req.max_tokens
            or self.positions[req.slot] + 2 >= self.config.max_seq
        )

    def _launch_decode(self, rows: list, behind: Optional[_DecodeStep] = None):
        """Build the one operand and launch a decode program in which
        ``rows`` are live. ``behind`` is the step launched before it and not
        yet read: its rows stand one position on and take their token from
        its output on the device; those of its rows that are not among
        ``rows`` end at it by count, and are left out as a free slot is (not
        live, position 0, table on the scratch block)."""
        t_launch = _time.monotonic()
        if self._no_prev is None:
            B, W = self.block_tables.shape
            i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
            small = jax.eval_shape(
                self._pg_decode, self.params, i32(B), i32(B, 4 + W), self.pool
            )[2]
            self._no_prev = jnp.zeros(small.shape, jnp.int32)
            if self.mesh is not None:
                self._no_prev = jax.device_put(self._no_prev, self._replicated)
        slots = [r.slot for r in rows]
        at = self.positions.copy()
        on, ended = [], []
        if behind is not None:
            theirs = {r.slot for r in behind.rows if not r.finished}
            on = sorted(theirs.intersection(slots))
            at[on] += 1
            ended = sorted(theirs.difference(slots))
        if self._window is not None:
            # The live rows' window tables, as of the position each writes:
            # a new block every block_size steps, one given back as often.
            w, bs = self._window, self._block_size
            here = at[slots]
            due = (here // bs >= w.hi[slots]) | (np.maximum(here - w.window + 1, 0) // bs > w.lo[slots])
            for slot in (s for s, d in zip(slots, due) if d):
                self._advance_window(slot, int(at[slot]), int(at[slot]) + 1)
        meta = np.concatenate(
            [
                at[:, None], np.zeros_like(at)[:, None],
                self.last_tokens[:, None], np.ones_like(at)[:, None],
                self.block_tables,
            ],
            axis=1,
        )
        meta[on, 3] = 0
        meta[ended] = 0
        meta[ended, 3] = 1
        meta[slots, 1] = 1
        self.stats[self._decode_arm] += 1
        if self._moe_arm:
            self.stats[self._moe_arm] += 1
        if self._state_arm:
            self.stats[self._state_arm] += 1
        self.stats["programs_launched"] += 1
        self.pool, logits, small = self._pg_decode(
            self.params, self._no_prev if behind is None else behind.small,
            meta, self.pool,
        )
        return _DecodeStep(
            rows, meta[slots, 0], logits, small, t_launch,
            self.stats["programs_launched"],
        )

    def _decode_turn(self, active: list, instrument: bool) -> list:
        """One turn's decode step over ``active``; returns the requests
        that finished at it. The step that is read is the one in flight, or
        one launched now where there is none. A turn that runs ahead
        (``_runs_ahead``) then launches the next turn's step, for the rows
        that go on and those admitted since, BEFORE it waits for this
        step's tokens, and reads ``[B]`` int32 where a synchronous turn
        reads the logits and calls ``_sample`` a row. A stop token is the
        one end learnt a step late: that row's next step is already
        launched, and is discarded when its turn comes."""
        fr = _flightrec.on()
        finished: list = []
        ahead = self._runs_ahead(active)
        cur, self._inflight = self._inflight, None
        if cur is not None and all(r.finished for r in cur.rows):
            cur = None  # computed for requests that had all ended: dropped
        # The span starts at the turn's first launch, so that the next
        # program to start on the device after it is a decode program (the
        # benchmark's readers pair a span with that run).
        t_dec = _time.monotonic()
        if cur is None:
            cur = self._launch_decode(active)
        nxt = None
        if ahead:
            reading = {id(r) for r in cur.rows}
            on = [
                r for r in active
                if id(r) not in reading or not self._ends_by_count(r)
            ]
            if on:
                nxt = self._launch_decode(on, behind=cur)
                self.stats["decode_steps_ahead"] += 1
        going = {id(r) for r in nxt.rows} if nxt is not None else ()
        t_disp = _time.monotonic() if fr else 0.0
        if nxt is None and cur.t_launch < t_dec:
            # A turn that launches nothing (every row of the step in flight
            # ends at it by count, or the turn is synchronous) has no
            # program of its own to start at: its span starts where the
            # step it reads was launched, in the turn before, with no
            # dispatch, and its wait for the tokens runs from there.
            t_dec = t_disp = cur.t_launch
        B = len(self._slot_req)
        logits_np = counters = None
        if ahead:
            small = np.asarray(cur.small)  # raylint: disable=RL101 -- the decode step's ONE intended sync: the tokens the program chose (and a family's counters), a few hundred bytes
            copied = small.nbytes
            chosen, counters = small[:B], small[B:]
        else:
            logits_np = np.asarray(cur.logits)  # raylint: disable=RL101 -- the synchronous arm's ONE intended sync: batched logits readback feeding host-side sampling
            copied = logits_np.nbytes
            if fr and not self._cache.hooks:
                counters = np.asarray(cur.small)[B:]  # raylint: disable=RL101 -- the programs' counters, ready with the logits
                copied += cur.small.nbytes
        t_read = _time.monotonic() if fr else 0.0
        now = _time.perf_counter()
        rows = [r for r in cur.rows if not r.finished]
        for req in rows:
            slot = req.slot
            self.positions[slot] += 1
            tok = (
                int(chosen[slot]) if logits_np is None
                else self._sample(logits_np[slot], req)
            )
            req.generated.append(tok)
            self.stats["tokens_generated"] += 1
            if instrument and req.t_last_token:
                _ITL_SECONDS.observe(now - req.t_last_token)
            req.t_last_token = now
            self.last_tokens[slot] = tok
            self._maybe_finish(req)
            if req.finished:
                finished.append(req)
                if id(req) in going:  # a stop token: its next row is waste
                    self.stats["decode_rows_discarded"] += 1
        self._inflight = nxt
        if fr:
            # Batch-wide phases (no rid). The step, and its three parts
            # end to end: building the operand and launching (the next
            # step's, when running ahead), the wait for this step's
            # tokens (or logits) with their copy, and the books of every
            # row (sampling too on the synchronous arm). ``batch`` is the
            # rows of the step that was read, ``discarded`` those of them
            # computed for nothing, ``ahead`` whether this turn launched
            # the next step before it read this one, ``seq`` and
            # ``next_seq`` the launch numbers of the step read and of the
            # step launched ahead (0: none).
            t_end = _time.monotonic()
            batch = len(cur.rows)
            # How much of the tables the traffic fills: the blocks
            # the live rows attend (ceil((position + 1) / block)
            # each) over the B x W entries a gather would bring back.
            bs = self._block_size
            blocks_live = int(((cur.at + bs) // bs).sum())
            moe = {}
            if counters is not None and not self._cache.hooks:
                # The rows a layer's attention reads, by the program's arm.
                in_place = self._decode_arm == "decode_attn_kernel_steps"
                blocks_read = blocks_live if in_place else self.block_tables.size
                moe = self._model.span_fields(
                    self.model_config, counters, batch, batch,
                    decode=(cur.at, blocks_read * bs),
                )
            if self._window is not None:
                # The rows of keys and values the step's attention needs in
                # a layer of each kind, those a window layer's program reads
                # (the blocks from the one that holds the window's first
                # position; every table whole under the gather), and the
                # window blocks held against what the slots would hold had
                # none been given back.
                w = self._window
                length = cur.at.astype(np.int64) + 1
                first = np.maximum(length - w.window, 0) // bs
                in_place = self._decode_arm == "decode_attn_kernel_steps"
                moe.update(
                    kv_rows_full=int(length.sum()),
                    kv_rows_window=int(np.minimum(length, w.window).sum()),
                    kv_rows_window_read=(
                        int(((cur.at // bs) - first + 1).sum()) if in_place
                        else len(self._slot_req) * self._table_width
                    ) * bs,
                    window_blocks_held=w.held_blocks,
                    blocks_full_retention=w.full_retention_blocks,
                )
            if self.config.prefill_chunk_tokens:
                # Slots mid-way through a prefill in chunks at this turn:
                # their chunks wait their turn (_advance_prefills).
                moe["chunks_pending"] = sum(
                    r is not None and r.prefilling for r in self._slot_req
                )
            _flightrec.record(
                "llm", "llm.decode_dispatch", t=t_dec,
                dur_s=t_disp - t_dec, batch=batch,
            )
            _flightrec.record(
                "llm", "llm.decode_readback", t=t_disp,
                dur_s=t_read - t_disp, bytes=copied,
            )
            _flightrec.record(
                "llm", "llm.decode_sample", t=t_read,
                dur_s=t_end - t_read, batch=batch,
            )
            _flightrec.record(
                "llm", "llm.decode_step", t=t_dec,
                dur_s=t_end - t_dec, batch=batch,
                kv_blocks_live=blocks_live,
                kv_blocks_table=self.block_tables.size,
                ahead=int(nxt is not None), discarded=batch - len(rows),
                seq=cur.seq, next_seq=nxt.seq if nxt is not None else 0,
                **moe,
            )
        return finished

    def _spec_eligible(self, active: list) -> bool:
        """A spec step is legal only when EVERY active slot is greedy with
        draft KV, and EVERY occupied slot (prefilling ones included: the
        fixed-shape verify writes k+1 garbage rows at their cursor, like
        vanilla decode writes one) sits k rows clear of max_seq — the
        bound that keeps every verify write inside the block table. All-
        or-nothing: the verify program is one fixed-shape batch; an
        ineligible step runs the vanilla program, token-identical."""
        k = self._spec.k
        lim = self.config.max_seq - 1
        for r in self._slot_req:
            if r is None:
                continue
            if self.positions[r.slot] + k > lim:
                return False
            if not r.prefilling and not (
                r.spec_ready and r.temperature <= 0.0
            ):
                return False
        return True

    def _publish_metrics(self) -> None:
        """Per-step gauge/counter publication: the generated-token delta
        since the last publish, KV-block utilization (the batching
        headroom signal), and the prefix-pool hit rate."""
        delta = self.stats["tokens_generated"] - self._published_tokens
        if delta:
            _GEN_TOKENS.inc(float(delta))
            self._published_tokens = self.stats["tokens_generated"]
        tags = _replica_tags()
        total = self.block_mgr.num_blocks - 1
        if total > 0:
            _KV_UTIL.set(self.block_mgr.used_blocks / total, tags)
        lookups = self.stats["prefix_lookups"]
        if lookups:
            _PREFIX_HIT_RATE.set(
                self.stats["prefix_hits"] / lookups, tags
            )

    # Advertisement cap: the pool's token budget already bounds the digest
    # count (budget / prefix_chunk), but a tiny chunk against a big budget
    # must not grow the per-heartbeat report unboundedly.
    MAX_ADVERTISED_DIGESTS = 512

    def _refresh_digest_snapshot(self) -> None:
        """Rebuild the routing advertisement from the pool and swap it in
        atomically (readers — the replica report loop — run on another
        thread; attribute assignment is their consistency boundary).
        Every chunk-multiple prefix of every pooled entry is advertised,
        so a router can match a PARTIAL share of a longer pooled prefix."""
        chunk = self.config.prefix_chunk
        out: set = set()
        for e in self._prefix_pool.values():
            out.update(chain_digests(e["tokens"], chunk, strict=False))
            if len(out) >= self.MAX_ADVERTISED_DIGESTS:
                break
        # Snapshot FIRST, version LAST: a report-thread read between the
        # two assignments must never pair the new version with the old
        # snapshot — that push would suppress the fresh digests until
        # the 5 s heartbeat (version is the report loop's push-now
        # signal). The benign race direction (old version + new
        # snapshot) just pushes one tick later.
        self._digest_snapshot = tuple(out)
        self._digest_version += 1

    def prefix_digest(self) -> dict:
        """Compact routing advertisement: what the prefix pool holds
        (stable cross-process digests at prefix_chunk granularity) plus
        the cache-pressure signals the router biases on. Thread-safe
        against the pump thread (snapshot tuple + scalar reads only)."""
        # Version BEFORE snapshot: paired with the writer's snapshot-then-
        # version order, a torn read can only pair an OLD version with a
        # NEW snapshot (pushes one tick late), never a new version with
        # stale digests (which would suppress the push until the 5 s
        # heartbeat).
        version = self._digest_version
        digests = list(self._digest_snapshot)
        lookups = self.stats["prefix_lookups"]
        total = self.block_mgr.num_blocks - 1
        kv_util = self.block_mgr.used_blocks / total if total > 0 else 0.0
        return {
            "scheme": (
                BYTE_BOS_SCHEME
                if isinstance(self.tokenizer, ByteTokenizer)
                else "custom"
            ),
            "chunk": self.config.prefix_chunk,
            "digests": digests,
            "version": version,
            "hit_rate": (self.stats["prefix_hits"] / lookups) if lookups else 0.0,
            "kv_util": kv_util,
            "prefill_tokens": self.stats["prefill_tokens"],
            "prefix_tokens_reused": self.stats["prefix_tokens_reused"],
        }

    def rolling_ttft_ms(self) -> float:
        """p95 of the recent-TTFT window, in milliseconds, counting only
        samples younger than TTFT_WINDOW_S (0.0 when none — an idle
        engine advertises recovery, so a TTFT-raised shed level can come
        back down). The serve controller compares this — advertised via
        router_state() — against the admission ttft watermarks."""
        cutoff = _time.monotonic() - self.TTFT_WINDOW_S
        window = sorted(v for t, v in list(self._ttft_window) if t >= cutoff)
        if not window:
            return 0.0
        idx = min(len(window) - 1, int(0.95 * len(window)))
        return round(window[idx] * 1e3, 3)

    def has_unfinished(self) -> bool:
        return any(not r.finished for r in self.requests.values())

    def kv_stats(self) -> dict:
        """Block-pool occupancy for routing/observability."""
        return {
            "paged": True,
            "block_size": self._block_size,
            "blocks_total": self.block_mgr.num_blocks - 1,
            "blocks_free": self.block_mgr.free_blocks,
            "blocks_used": self.block_mgr.used_blocks,
        }

    def pop_finished(self) -> list:
        done = [r for r in self.requests.values() if r.finished]
        for r in done:
            del self.requests[r.request_id]
        return done

    # -- convenience -----------------------------------------------------------
    def generate(
        self, prompts: list, sampling: SamplingParams | None = None
    ) -> list[dict]:
        """Blocking batch generation; returns [{text, token_ids}] in order."""
        base = self._steps
        ids = [f"gen-{base}-{i}" for i in range(len(prompts))]
        for rid, p in zip(ids, prompts):
            self.add_request(rid, p, sampling)
        while self.has_unfinished():
            self.step()
        done = {r.request_id: r for r in self.pop_finished()}
        out = []
        for rid in ids:
            req = done[rid]
            toks = [
                t for t in req.generated if t != req.stop_token
            ]
            out.append(
                {
                    "request_id": rid,
                    "token_ids": list(req.generated),
                    "text": self.tokenizer.decode(toks),
                    "num_generated": len(req.generated),
                    "error": req.error,
                }
            )
        return out
