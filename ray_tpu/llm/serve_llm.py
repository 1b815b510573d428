"""serve.llm: OpenAI-compatible serving on the Serve tier.

Reference parity: python/ray/llm/_internal/serve/ (LLMServer deployment +
OpenAI-compatible router). The replica owns one LLMEngine pinned to its
actor's devices; an asyncio pump loop runs the engine's continuous-batching
steps while requests await their finish events, so concurrent HTTP requests
batch onto the same decode step.

Endpoints (via the Serve HTTP proxy, path-routed to this deployment):
  POST /{name}/v1/completions       {"prompt": ..., "max_tokens": ...}
  POST /{name}/v1/chat/completions  {"messages": [{role, content}...]}
"""

from __future__ import annotations

import asyncio
import itertools
import threading
import time

from ray_tpu.llm.config import LLMConfig, SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.serve import api as serve_api
from ray_tpu.util import flightrec as _flightrec
from ray_tpu.util import metrics as _metrics
from ray_tpu.util.compile_cache import CacheCounter
from ray_tpu.util.tasks import spawn

# Replica-level serving view on top of the engine's own series (TTFT/ITL/
# token counters/KV gauges live in llm/engine.py): how long each
# continuous-batching step holds the executor thread and how many requests
# are riding the batch.
_STEP_SECONDS = _metrics.Histogram(
    "raytpu_llm_engine_step_seconds",
    "wall time of one continuous-batching step (admissions included)",
    boundaries=_metrics.LATENCY_BOUNDARIES_S,
)
_ACTIVE_REQUESTS = _metrics.Gauge(
    "raytpu_llm_active_requests",
    "requests admitted or decoding on this engine replica",
    tag_keys=("replica",),  # gauge: untagged would last-wins across replicas
)


class LLMServer:
    """The deployment callable (one engine per replica)."""

    def __init__(self, config: LLMConfig):
        self.config = config
        self._cache_counter = CacheCounter()  # before the engine compiles
        self.engine = LLMEngine(config)
        self._counter = itertools.count()
        self._finished: dict[str, object] = {}  # request_id -> _Request
        self._events: dict[str, asyncio.Event] = {}
        # Token streaming: request_id -> queue of decoded token ids (None =
        # end of stream), fed by the pump after each decode step.
        self._token_queues: dict[str, asyncio.Queue] = {}
        self._delivered: dict[str, int] = {}  # tokens pushed so far
        # Thread-safety: the engine is touched ONLY by the pump's executor
        # thread. The event loop enqueues admissions here; the pump drains
        # them into the engine at step boundaries (a direct add_request from
        # the loop would mutate engine.requests while step() iterates it).
        self._pending: list[tuple] = []
        self._pending_lock = threading.Lock()
        self._pump_task = None
        # Monotonic time the last engine step returned while the pump
        # still had work (None once it ran dry): the next step's entry
        # closes the flight recorder's llm.pump_gap span from it.
        self._t_step_returned: float | None = None
        # Monotonic time the last step of a pump that ran dry returned (None
        # while a pump runs): the start of the flight recorder's llm.idle
        # span, which the start of the next pump closes.
        self._t_ran_dry: float | None = None

    def _ensure_pump(self) -> None:
        if self._pump_task is None or self._pump_task.done():
            if self._t_ran_dry is not None and _flightrec.on():
                # The dry spell: nothing to serve from the last step's return
                # until now. With llm.step and llm.pump_gap it tiles the
                # engine's life, so a device that idles under it idles for
                # want of a request and not for a late host.
                _flightrec.record(
                    "llm", "llm.idle", t=self._t_ran_dry,
                    dur_s=time.monotonic() - self._t_ran_dry,
                )
            self._t_ran_dry = None
            self._pump_task = spawn(self._pump(), name="llm engine pump")

    def _step_with_admissions(self) -> list:
        fr = _flightrec.on()
        t_in = time.monotonic()
        with self._pending_lock:
            batch, self._pending = self._pending, []
        if fr and self._t_step_returned is not None:
            # The hop between two turns of a busy engine: back to the
            # event loop, the token push, and into the executor again.
            _flightrec.record(
                "llm", "llm.pump_gap", t=self._t_step_returned,
                dur_s=t_in - self._t_step_returned, pending=len(batch),
            )
        for rid, prompt, sampling, prefill_only, handoff, t_queued in batch:
            if handoff is not None:
                self.engine.add_handoff_request(
                    rid, handoff, sampling, t_queued=t_queued
                )
            else:
                self.engine.add_request(
                    rid, prompt, sampling, prefill_only=prefill_only,
                    t_queued=t_queued,
                )
        finished = self.engine.step()
        for req in finished:
            self.engine.requests.pop(req.request_id, None)
        more = self.engine.has_unfinished()
        self._t_step_returned = time.monotonic()
        if fr:
            # This whole turn in the executor thread. With llm.pump_gap it
            # tiles a busy engine's time without a seam, so that whatever
            # the engine's finer spans (admit, prefill, the decode step
            # and its parts) leave open still has the engine's name on it.
            _flightrec.record(
                "llm", "llm.step", t=t_in,
                dur_s=self._t_step_returned - t_in,
                admitted=len(batch), finished=len(finished),
            )
        return finished, more

    def _push_new_tokens(self, finished: list) -> None:
        """Between steps (engine quiescent): forward newly generated tokens
        of streaming requests to their queues; None terminates a stream."""
        live = list(self.engine.requests.values()) + list(finished)
        for req in live:
            q = self._token_queues.get(req.request_id)
            if q is None:
                continue
            sent = self._delivered.get(req.request_id, 0)
            for tok in req.generated[sent:]:
                q.put_nowait(tok)
            self._delivered[req.request_id] = len(req.generated)
        for req in finished:
            q = self._token_queues.get(req.request_id)
            if q is not None:
                q.put_nowait(None)

    async def _pump(self) -> None:
        """Engine loop: steps while work exists, yields to the event loop
        between steps so new requests can join the batch."""
        loop = asyncio.get_running_loop()
        while True:
            instrument = _metrics.metrics_enabled()
            t0 = time.perf_counter() if instrument else 0.0
            finished, more = await loop.run_in_executor(
                None, self._step_with_admissions
            )
            if instrument:
                from ray_tpu.llm.engine import _replica_tags

                _STEP_SECONDS.observe(time.perf_counter() - t0)
                _ACTIVE_REQUESTS.set(
                    float(len(self.engine.requests)), _replica_tags()
                )
            t_push = time.monotonic()
            self._push_new_tokens(finished)
            for req in finished:
                self._finished[req.request_id] = req
                ev = self._events.pop(req.request_id, None)
                if ev is not None:
                    ev.set()
            if _flightrec.on():
                _flightrec.record(
                    "llm", "llm.push_tokens", t=t_push,
                    dur_s=time.monotonic() - t_push,
                    streams=len(self._token_queues),
                )
            with self._pending_lock:
                if not more and not self._pending:
                    # ran dry: no gap to name, and llm.idle starts here
                    self._t_ran_dry, self._t_step_returned = self._t_step_returned, None
                    return

    def _admit(
        self,
        prompt,
        sampling: SamplingParams,
        prefill_only: bool = False,
        handoff: dict | None = None,
    ) -> str:
        rid = f"req-{next(self._counter)}"
        if _flightrec.on():
            # Stitch the router's flight-recorder request id (propagated via
            # the replica's contextvar) to the engine-local req-N id, so the
            # timeline exporter can join serve hops to engine phases.
            from ray_tpu.serve.replica import current_frid

            frid = current_frid()
            if frid is not None:
                _flightrec.record("llm", "llm.bind", rid=rid, frid=frid)
        with self._pending_lock:
            # The hand-over time rides along: the engine's llm.queue span
            # starts here, not where the pump drains the list.
            self._pending.append(
                (rid, prompt, sampling, prefill_only, handoff, time.monotonic())
            )
        return rid

    async def _generate(
        self, prompt, sampling: SamplingParams, handoff: dict | None = None
    ) -> dict:
        rid = self._admit(prompt, sampling, handoff=handoff)
        ev = asyncio.Event()
        self._events[rid] = ev
        self._ensure_pump()
        await ev.wait()
        req = self._finished.pop(rid)
        toks = [t for t in req.generated if t != req.stop_token]
        return {
            "text": self.engine.tokenizer.decode(toks),
            "token_ids": list(req.generated),
            "num_generated": len(req.generated),
            # Admission failure (e.g. a reservation the KV pool can never
            # satisfy): the engine finishes the request with req.error set
            # instead of wedging; it must not leave here as an empty 200.
            "error": getattr(req, "error", None),
        }

    async def _stream_tokens(
        self, prompt, sampling: SamplingParams, handoff: dict | None = None
    ):
        """Async generator of decoded text pieces, one per generated token,
        emitted as each decode step lands (true token streaming: the chip is
        still decoding later tokens while early ones are on the wire)."""
        rid = self._admit(prompt, sampling, handoff=handoff)
        q: asyncio.Queue = asyncio.Queue()
        self._token_queues[rid] = q
        ev = asyncio.Event()
        self._events[rid] = ev
        self._ensure_pump()
        try:
            while True:
                tok = await q.get()
                if tok is None:
                    done = self._finished.get(rid)
                    if done is not None and getattr(done, "error", None):
                        # Surface through the SSE error channel (the proxy
                        # emits a data: {"error": ...} event + [DONE]).
                        raise RuntimeError(done.error)
                    break
                req = self.engine.requests.get(rid) or self._finished.get(rid)
                if req is not None and tok == req.stop_token:
                    continue
                yield self.engine.tokenizer.decode([tok])
        finally:
            self._token_queues.pop(rid, None)
            self._delivered.pop(rid, None)
            self._finished.pop(rid, None)
            self._events.pop(rid, None)

    def engine_report(self) -> dict:
        """What this replica runs on and what its engine has done so far,
        read inside the replica's own process (``chip_smoke.py`` takes its
        evidence from here; reach it with
        ``ray_tpu.ActorHandle(replica_id, "Replica").handle``)."""
        import os

        import jax

        devices = jax.devices()
        return {
            "pid": os.getpid(),
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_ids": [d.id for d in devices],
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "stats": dict(self.engine.stats),
            "compile_cache": self._cache_counter.snapshot(),
        }

    def router_state(self) -> dict:
        """Routing advertisement, pushed by the hosting ReplicaActor's
        report loop: which prefix blocks this replica's KV pool already
        holds (stable digests), plus hit-rate/KV-util — the signals the
        prefix-affinity router biases pow-2 on — and the rolling p95 TTFT
        the serve controller's overload watermarks compare against. Reads
        only atomic engine snapshots, so it is safe against the pump's
        executor thread."""
        state = self.engine.prefix_digest()
        state["ttft_ms"] = self.engine.rolling_ttft_ms()
        return state

    @staticmethod
    def _sampling(body: dict) -> SamplingParams:
        return SamplingParams(
            max_tokens=int(body.get("max_tokens", 16)),
            temperature=float(body.get("temperature", 0.0)),
        )

    @staticmethod
    def _prompt_of(request: dict) -> str:
        """The prompt text this replica will tokenize — the same rules the
        router's _extract_prompt mirrors (chat path -> the shared
        chat_prompt join, everything else -> body['prompt'])."""
        body = request.get("body") or {}
        if not isinstance(body, dict):
            return ""
        if str(request.get("path", "")).endswith("/v1/chat/completions"):
            from ray_tpu.util.prefix_digest import chat_prompt

            msgs = body.get("messages", [])
            return chat_prompt(msgs if isinstance(msgs, list) else [])
        return body.get("prompt", "")

    async def prefill_handoff(self, request: dict) -> dict:
        """Prefill leg of the disaggregated two-hop (router-invoked on
        prefill-role replicas): run admission + prefill for the request's
        prompt, sample the first token, and return the handoff descriptor
        — prompt ids, the first token, and the armed KV-block export the
        decode replica pulls over the transfer fabric. Returns
        {"unsupported": True} when this replica cannot export (the
        RAY_TPU_DISAGG kill switch landed here first) — the router then
        falls back to unified routing."""
        from ray_tpu.core.config import GLOBAL_CONFIG

        if not GLOBAL_CONFIG.disagg:
            return {"unsupported": True}
        body = request.get("body") or {}
        if not isinstance(body, dict):
            return {"error": "JSON body required"}
        rid = self._admit(
            self._prompt_of(request), self._sampling(body), prefill_only=True
        )
        ev = asyncio.Event()
        self._events[rid] = ev
        self._ensure_pump()
        await ev.wait()
        req = self._finished.pop(rid)
        if getattr(req, "error", None):
            return {"error": req.error}
        return req.handoff_out or {"unsupported": True}

    def _stream_chunks(
        self, prompt, body: dict, created: int, chat: bool,
        handoff: dict | None = None,
    ):
        """OpenAI-convention chunk objects (chat.completion.chunk /
        text_completion chunks), one per token, + a finish_reason tail."""

        async def chunks():
            idx = 0
            async for piece in self._stream_tokens(
                prompt, self._sampling(body), handoff=handoff
            ):
                idx += 1
                if chat:
                    yield {
                        "id": "chatcmpl-raytpu",
                        "object": "chat.completion.chunk",
                        "created": created,
                        "model": self.config.model_id,
                        "choices": [
                            {
                                "index": 0,
                                "delta": {"content": piece},
                                "finish_reason": None,
                            }
                        ],
                    }
                else:
                    yield {
                        "id": "cmpl-raytpu",
                        "object": "text_completion",
                        "created": created,
                        "model": self.config.model_id,
                        "choices": [
                            {"index": 0, "text": piece,
                             "finish_reason": None}
                        ],
                    }
            tail_choice = (
                {"index": 0, "delta": {}, "finish_reason": "stop"}
                if chat
                else {"index": 0, "text": "", "finish_reason": "stop"}
            )
            yield {
                "id": "chatcmpl-raytpu" if chat else "cmpl-raytpu",
                "object": (
                    "chat.completion.chunk" if chat else "text_completion"
                ),
                "created": created,
                "model": self.config.model_id,
                "choices": [tail_choice],
                "usage": {"completion_tokens": idx},
            }

        return chunks()

    async def __call__(self, request: dict):
        path = request.get("path", "")
        body = request.get("body") or {}
        if not isinstance(body, dict):
            return {"error": "JSON body required"}
        created = int(time.time())
        # Disaggregated two-hop: the router attaches the prefill replica's
        # handoff; this (decode) replica joins the request mid-decode.
        handoff = request.get("_handoff")
        if path.endswith("/v1/chat/completions"):
            # ONE prompt-derivation rule (shared with prefill_handoff —
            # the handoff pairing depends on both replicas deriving the
            # same text the shipped KV encodes).
            prompt = self._prompt_of(request)
            if body.get("stream"):
                return self._stream_chunks(
                    prompt, body, created, chat=True, handoff=handoff
                )
            out = await self._generate(
                prompt, self._sampling(body), handoff=handoff
            )
            if out.get("error"):
                return {"error": out["error"]}
            return {
                "id": "chatcmpl-raytpu",
                "object": "chat.completion",
                "created": created,
                "model": self.config.model_id,
                "choices": [
                    {
                        "index": 0,
                        "message": {
                            "role": "assistant",
                            "content": out["text"],
                        },
                        "finish_reason": "stop",
                    }
                ],
                "usage": {"completion_tokens": out["num_generated"]},
            }
        # default: completions
        prompt = self._prompt_of(request)
        if body.get("stream"):
            return self._stream_chunks(
                prompt, body, created, chat=False, handoff=handoff
            )
        out = await self._generate(
            prompt, self._sampling(body), handoff=handoff
        )
        if out.get("error"):
            return {"error": out["error"]}
        return {
            "id": "cmpl-raytpu",
            "object": "text_completion",
            "created": created,
            "model": self.config.model_id,
            "choices": [
                {"index": 0, "text": out["text"], "finish_reason": "stop"}
            ],
            "usage": {"completion_tokens": out["num_generated"]},
        }


def build_openai_app(
    config: LLMConfig,
    *,
    name: str = "llm",
    num_replicas: int = 1,
    admission_config: dict | None = None,
    prefill_replicas: int = 0,
):
    """An Application serving OpenAI-style routes under /{name}/v1/...
    (reference: ray.serve.llm build_openai_app). ``admission_config``
    opts the deployment into the serve overload plane (tenant token
    buckets, priority shedding on queue/TTFT watermarks, bounded replica
    queues — see README "Overload protection"); LLM replicas advertise a
    rolling p95 TTFT, so the ttft_high_ms/ttft_low_ms watermarks are
    live for this deployment.

    A replica executes as many requests at once as its engine has slots:
    the deployment's ``max_concurrent_queries`` is ``config.max_slots``,
    not the cluster default ``serve_max_concurrent``. The decode program
    always runs ``max_slots`` rows, so a narrower replica pays for rows
    it never fills. The routing table's ``max_concurrent``, the replica
    actor's ``max_concurrency`` (two more: they wait in the engine's own
    queue, so the admitting turn refills a freed slot), the execution
    gate and the bounded queue's cap all follow from that one number, in
    both roles of a disaggregated deployment.

    ``prefill_replicas`` > 0 opts into DISAGGREGATED serving: the
    deployment runs ``prefill_replicas`` prefill-role replicas plus
    ``num_replicas`` decode-role replicas, roles advertised in the
    routing table. The router lands each request's prefill on a prefill
    replica (prefix-digest bias preserved), ships the finished KV blocks
    to a decode replica over the transfer fabric (the handoff carries the
    first sampled token), and decode replicas never run whole-suffix
    prefill — see README "Disaggregated serving". RAY_TPU_DISAGG=0
    restores unified serving byte-identically."""
    from ray_tpu.util.prefix_digest import BYTE_BOS_SCHEME

    disagg_config = None
    if prefill_replicas > 0:
        disagg_config = {"prefill_replicas": int(prefill_replicas)}
        num_replicas = int(num_replicas) + int(prefill_replicas)
    dep = serve_api.deployment(
        LLMServer,
        name=name,
        num_replicas=num_replicas,
        max_concurrent_queries=config.max_slots,
        admission_config=admission_config,
        disagg_config=disagg_config,
        ray_actor_options=dict(config.placement),
        # Same-prefix requests stick to a replica whose engine already
        # pooled that prefix's KV (no re-prefill of shared system prompts).
        request_affinity=(
            "prompt_prefix" if config.enable_prefix_caching else None
        ),
        # Digest contract for prefix-affinity routing: the engine's
        # default ByteTokenizer is byte-level, so routers can hash a
        # prompt's leading blocks from TEXT and match the replica-pooled
        # digests exactly (a custom tokenizer would advertise "custom"
        # and routers fall back to load-only).
        request_affinity_config=(
            {"scheme": BYTE_BOS_SCHEME, "chunk": config.prefix_chunk}
            if config.enable_prefix_caching
            else None
        ),
    )
    return dep.bind(config)
