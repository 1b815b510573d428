"""Control-plane microbenchmarks (reference: python/ray/_private/ray_perf.py).

Measures task/actor/object throughput of the ray_tpu runtime on one machine
and prints one line per metric. Run:

    python tools/ray_perf.py [--quick]

Results are checked into PERF.md next to BASELINE.md's reference numbers.
NOTE: the dev box has ONE physical core shared by driver + GCS + node +
workers; the reference numbers were taken on an m5.16xlarge (64 vCPU) head,
so absolute comparisons carry a large machine handicap.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

import ray_tpu


def timeit(name, fn, multiplier=1, warmup=1, min_s=2.0, max_iters=50):
    for _ in range(warmup):
        fn()
    start = time.perf_counter()
    iters = 0
    while True:
        fn()
        iters += 1
        elapsed = time.perf_counter() - start
        if elapsed > min_s or iters >= max_iters:
            break
    rate = multiplier * iters / elapsed
    print(f"{name}: {rate:,.1f} /s", flush=True)
    return name, rate


@ray_tpu.remote
def tiny():
    return b"ok"


@ray_tpu.remote
class Sink:
    def ping(self):
        return b"ok"

    def with_arg(self, x):
        return b"ok"

    async def aping(self):
        return b"ok"


def _p99_ms(samples: list) -> float:
    if not samples:
        return 0.0
    s = sorted(samples)
    return round(s[min(len(s) - 1, int(0.99 * len(s)))] * 1e3, 2)


def _hist_snapshot(name: str) -> dict:
    """Cumulative bucket counts (le -> count, summed across processes)
    of one merged-cluster histogram, from the Prometheus exposition.
    Engine-side phase deltas come from diffing two of these — client
    timings on a contended box carry scheduler noise the engine's own
    step clock does not."""
    from ray_tpu.util.state.api import cluster_metrics_text

    out: dict = {}
    for line in cluster_metrics_text().splitlines():
        if not line.startswith(name + "_bucket"):
            continue
        try:
            le = line.split('le="', 1)[1].split('"', 1)[0]
            out[le] = out.get(le, 0.0) + float(line.rsplit(None, 1)[1])
        except (IndexError, ValueError):
            continue
    return out


def _hist_frac_above(before: dict, after: dict, boundary: str) -> float:
    """Fraction of NEW samples (between two snapshots) above ``boundary``
    seconds; -1 when the window saw no samples."""
    d = {le: after.get(le, 0.0) - before.get(le, 0.0) for le in after}
    total = d.get("+Inf", 0.0)
    if total <= 0:
        return -1.0
    return round((total - d.get(boundary, 0.0)) / total, 4)


def _serve_llm_rows(
    results: dict,
    no_chunked_prefill: bool,
    quick: bool,
    no_disagg: bool = False,
    no_spec_decode: bool = False,
):
    """Cache-aware LLM serving rows (PERF.md round-12): two tiny-model
    replicas behind the serve router, streaming clients from driver
    threads. Two traffic mixes:

      serve_llm_shared_prefix — 3 long shared system prompts x unique
        suffixes at high concurrency: prefix-affinity routing converges
        each prompt family onto the replica that pooled it (tok/s + p99
        TTFT vs --no-prefix-routing).
      serve_llm_mixed_len — long prompts interleaved with short in-flight
        decoders: chunked prefill bounds the decoders' p99 ITL (vs
        --no-chunked-prefill).
    """
    import concurrent.futures

    from ray_tpu import serve
    from ray_tpu.core.config import GLOBAL_CONFIG
    from ray_tpu.llm.config import LLMConfig
    from ray_tpu.llm.serve_llm import build_openai_app
    from ray_tpu.models.gpt2 import GPT2Config

    # Sized so prefill is a real cost on CPU (the TPU-serving regime the
    # A/B models): a cold ~900-token prompt costs several decode steps,
    # so a missed cache reuse / an unchunked prefill stall is visible.
    # The prompt families share a 260-char boilerplate header then
    # DIVERGE — the pre-round-12 px: affinity (first 256 chars) cannot
    # tell them apart, block digests can — and the pool budget holds
    # only 2 of the 3 families per replica, so the families must
    # PARTITION across replicas to all stay warm.
    # Faster digest repair for the benchmark: one ~900-token request on
    # this box takes ~0.5 s, so the default 2 s staleness window lets a
    # single pool-churn event misroute several follow-ups; 0.75 s keeps
    # the table within ~1-2 requests of reality (documented knob — a
    # real deployment with ms-scale requests would RAISE it instead).
    GLOBAL_CONFIG.prefix_route_staleness_s = min(
        GLOBAL_CONFIG.prefix_route_staleness_s, 0.75
    )
    model = GPT2Config.tiny(n_layer=3, d_model=256, n_head=4, max_seq=1024)
    cfg = LLMConfig(
        model_config=model,
        max_slots=4,
        max_seq=1024,
        prefill_buckets=(32, 128, 1024),
        num_kv_blocks=420,
        prefix_chunk=32,
        max_prefix_cache_tokens=2048,
        prefill_chunk_tokens=0 if no_chunked_prefill else 128,
    )
    handle = serve.run(build_openai_app(cfg, name="perfllm", num_replicas=2))
    stream_handle = handle.options(stream=True)
    common = (
        "SYSTEM BOILERPLATE: you are a careful, terse assistant; follow "
        "the contract; cite sources; refuse what you must refuse; " * 2
    )[:260]
    # THREE families over two replicas whose pools hold TWO ~900-token
    # entries each: a stable {2 families, 1 family} partition exists and
    # digest routing maintains it (a correctly routed request refreshes
    # its own entry, evicting nothing); cache-blind routing bounces the
    # shared-header traffic and thrashes the 2-entry pools.
    systems = [
        common
        + f" FAMILY {i}: "
        + f"domain-{i} instructions and few-shot examples; " * 14
        for i in range(3)
    ]  # ~900 chars each: a full 1024-token prefill bucket when cold

    def one_request(prompt: str, max_tokens: int) -> dict:
        t0 = time.perf_counter()
        ttft, gaps, last, tokens = None, [], None, 0
        for _chunk in stream_handle.remote(
            {
                "path": "/perfllm/v1/completions",
                "body": {
                    "prompt": prompt,
                    "max_tokens": max_tokens,
                    "stream": True,
                },
            }
        ):
            now = time.perf_counter()
            if ttft is None:
                ttft = now - t0
            elif last is not None:
                gaps.append(now - last)
            last = now
            tokens += 1
        return {"ttft": ttft or 0.0, "gaps": gaps, "tokens": tokens}

    def run_mix(requests: list, workers: int) -> list:
        out = [None] * len(requests)
        with concurrent.futures.ThreadPoolExecutor(workers) as pool:
            futs = {
                pool.submit(one_request, p, mt): i
                for i, (p, mt) in enumerate(requests)
            }
            for f in concurrent.futures.as_completed(futs):
                out[futs[f]] = f.result()
        return out

    n_shared = 24 if quick else 60
    n_long = 4 if quick else 10
    n_short = 12 if quick else 30

    # Warm each prompt family twice (pass 1 pools by pow-2 wherever it
    # lands; pass 2, past the staleness window, routes on the advertised
    # digests and repairs any churn), so both arms measure steady-state
    # serving, not cold-start discovery.
    for _pass in range(2):
        for s in systems:
            one_request(s + " warmup", 2)
        time.sleep(GLOBAL_CONFIG.prefix_route_staleness_s + 1.5)

    shared_reqs = [
        (systems[i % len(systems)] + f" q{i}", 8) for i in range(n_shared)
    ]
    pre_hist = _hist_snapshot("raytpu_llm_ttft_seconds")
    t0 = time.perf_counter()
    res = run_mix(shared_reqs, workers=6)
    dt = time.perf_counter() - t0
    toks = sum(r["tokens"] for r in res)
    results["serve_llm_shared_prefix"] = round(toks / dt, 1)
    results["serve_llm_shared_prefix_p99_ttft_ms"] = _p99_ms(
        [r["ttft"] for r in res]
    )
    time.sleep(3.0)  # metric push interval: let replica snapshots land
    results["serve_llm_shared_ttft_gt250ms_pct"] = _hist_frac_above(
        pre_hist, _hist_snapshot("raytpu_llm_ttft_seconds"), "0.25"
    )
    print(
        f"serve_llm_shared_prefix: {results['serve_llm_shared_prefix']:,} "
        f"tok/s, p99 TTFT "
        f"{results['serve_llm_shared_prefix_p99_ttft_ms']} ms, engine "
        f"TTFT>250ms {results['serve_llm_shared_ttft_gt250ms_pct']:.1%}",
        flush=True,
    )

    # Mixed lengths: short decoders in flight while long COLD prompts
    # prefill (each long prompt is distinct — no cache help; unchunked,
    # its full-bucket prefill stalls every decoder sharing the replica).
    mixed = [
        (f"COLD DOCUMENT {i}: " + f"paragraph {i} " * 120, 8)
        for i in range(n_long)
    ] + [(f"quick question {i}?", 24) for i in range(n_short)]
    pre_hist = _hist_snapshot("raytpu_llm_itl_seconds")
    t0 = time.perf_counter()
    res = run_mix(mixed, workers=6)
    dt = time.perf_counter() - t0
    toks = sum(r["tokens"] for r in res)
    short_gaps = [g for r in res[n_long:] for g in r["gaps"]]
    results["serve_llm_mixed_len"] = round(toks / dt, 1)
    results["serve_llm_mixed_len_p99_ttft_ms"] = _p99_ms(
        [r["ttft"] for r in res]
    )
    results["serve_llm_mixed_len_p99_itl_ms"] = _p99_ms(short_gaps)
    time.sleep(3.0)
    # The stall criterion, on the engine's own clock: the share of
    # decode-loop inter-token gaps above 100 ms — an unchunked ~1024-token
    # prefill (~120 ms on this box) parks every in-flight decoder in the
    # >100 ms buckets; chunked prefill must empty them.
    results["serve_llm_mixed_itl_gt100ms_pct"] = _hist_frac_above(
        pre_hist, _hist_snapshot("raytpu_llm_itl_seconds"), "0.1"
    )
    print(
        f"serve_llm_mixed_len: {results['serve_llm_mixed_len']:,} tok/s, "
        f"p99 TTFT {results['serve_llm_mixed_len_p99_ttft_ms']} ms, "
        f"short-stream p99 ITL "
        f"{results['serve_llm_mixed_len_p99_itl_ms']} ms, engine "
        f"ITL>100ms {results['serve_llm_mixed_itl_gt100ms_pct']:.1%}",
        flush=True,
    )

    # Engine-side aggregates via the advertisement table: prefill_tokens
    # is the compute actually paid, prefix_tokens_reused the compute
    # routing+caching avoided — the mechanism behind the client metrics.
    time.sleep(2.0)  # let the last report-loop push land
    ctrl = ray_tpu.get_actor("serve::controller")
    st = ray_tpu.get(ctrl.get_router_state.remote("perfllm"), timeout=30)
    results["serve_llm_prefill_tokens"] = float(
        sum(
            ((i.get("state") or {}).get("prefill_tokens", 0))
            for i in st.values()
        )
    )
    results["serve_llm_prefix_tokens_reused"] = float(
        sum(
            ((i.get("state") or {}).get("prefix_tokens_reused", 0))
            for i in st.values()
        )
    )
    print(
        f"  engines: {results['serve_llm_prefill_tokens']:.0f} prefill "
        f"tokens paid, {results['serve_llm_prefix_tokens_reused']:.0f} "
        f"reused",
        flush=True,
    )

    # Routing outcome counters from THIS process (the router runs here).
    from ray_tpu.util.metrics import registry

    for name, key in (
        ("raytpu_serve_prefix_route_hits_total", "serve_llm_route_hits"),
        ("raytpu_serve_prefix_route_misses_total", "serve_llm_route_misses"),
    ):
        total = 0.0
        for n, _tags, v in registry().snapshot()["points"]:
            if n == name:
                total += v
        results[key] = total
    print(
        f"  routing: {results['serve_llm_route_hits']:.0f} hits / "
        f"{results['serve_llm_route_misses']:.0f} misses",
        flush=True,
    )
    serve.shutdown()

    # Controlled single-engine stall probe (no serve/driver noise, both
    # cores to one process): the worst inter-token gap three in-flight
    # decoders see while a cold ~950-token prompt is admitted — THE
    # number chunked prefill exists to bound. Unchunked, the gap is one
    # full-bucket prefill + a step; chunked, one chunk + a step.
    import statistics

    from ray_tpu.llm.config import SamplingParams
    from ray_tpu.llm.engine import LLMEngine

    eng = LLMEngine(
        LLMConfig(
            model_config=model,
            max_slots=4,
            max_seq=1024,
            prefill_buckets=(32, 128, 1024),
            num_kv_blocks=420,
            enable_prefix_caching=False,  # every long prompt stays cold
            prefill_chunk_tokens=0 if no_chunked_prefill else 128,
        )
    )
    eng.add_request("warm", "w" * 950, SamplingParams(max_tokens=2))
    while eng.has_unfinished():
        eng.step()  # compile both prefill paths + decode
    eng.pop_finished()
    for i in range(3):
        eng.add_request(f"d{i}", f"short {i}", SamplingParams(max_tokens=250))
    eng.step()
    eng.step()
    stalls = []
    for trial in range(3):
        eng.add_request(
            f"long{trial}", "y" * (930 + trial), SamplingParams(max_tokens=2)
        )
        gaps, t_last = [], time.perf_counter()
        for _ in range(64):
            eng.step()
            now = time.perf_counter()
            gaps.append(now - t_last)
            t_last = now
            if not any(
                r.request_id == f"long{trial}" and not r.finished
                for r in eng.requests.values()
            ):
                break
        eng.pop_finished()
        stalls.append(max(gaps))
    results["serve_llm_decode_stall_ms"] = round(
        statistics.median(stalls) * 1e3, 2
    )
    print(
        f"serve_llm_decode_stall_ms: "
        f"{results['serve_llm_decode_stall_ms']} ms (worst decoder gap "
        f"while a cold long prompt lands; median of 3)",
        flush=True,
    )

    # Disaggregated-serving stall probe (round 16): the same worst-gap
    # question, but the decode engine takes the long prompt as a KV
    # HANDOFF prefilled on a separate engine (the prefill tier) instead
    # of prefilling it locally — the decode clock pays only the pull +
    # scatter. --no-disagg is the OFF arm (local admission, = the
    # round-12 number).
    from ray_tpu.llm.engine import LLMEngine as _Eng

    probe_cfg = LLMConfig(
        model_config=model,
        max_slots=4,
        max_seq=1024,
        prefill_buckets=(32, 128, 1024),
        num_kv_blocks=420,
        enable_prefix_caching=False,
        prefill_chunk_tokens=0 if no_chunked_prefill else 128,
    )
    dec = _Eng(probe_cfg)
    pre = None if no_disagg else _Eng(probe_cfg)
    # Warm/compile every path each arm uses (prefill buckets, decode,
    # and — ON arm — the handoff gather/pull/scatter programs).
    dec.add_request("warm", "w" * 950, SamplingParams(max_tokens=2))
    while dec.has_unfinished():
        dec.step()
    dec.pop_finished()
    if pre is not None:
        pre.add_request(
            "warmp", "w" * 950, SamplingParams(max_tokens=2),
            prefill_only=True,
        )
        while pre.has_unfinished():
            pre.step()
        dec.add_handoff_request(
            "warmh", pre.pop_finished()[0].handoff_out,
            SamplingParams(max_tokens=2),
        )
        while dec.has_unfinished():
            dec.step()
        dec.pop_finished()
    for i in range(3):
        dec.add_request(
            f"dd{i}", f"short {i}", SamplingParams(max_tokens=250)
        )
    dec.step()
    dec.step()
    stalls = []
    for trial in range(3):
        rid = f"dlong{trial}"
        prompt = "y" * (930 + trial)
        if pre is None:
            dec.add_request(rid, prompt, SamplingParams(max_tokens=2))
        else:
            pre.add_request(
                rid, prompt, SamplingParams(max_tokens=2),
                prefill_only=True,
            )
            while pre.has_unfinished():
                pre.step()  # the prefill tier's clock, not the decoders'
            dec.add_handoff_request(
                rid, pre.pop_finished()[0].handoff_out,
                SamplingParams(max_tokens=2),
            )
        gaps, t_last = [], time.perf_counter()
        for _ in range(64):
            dec.step()
            now = time.perf_counter()
            gaps.append(now - t_last)
            t_last = now
            if not any(
                r.request_id == rid and not r.finished
                for r in dec.requests.values()
            ):
                break
        dec.pop_finished()
        stalls.append(max(gaps))
    results["serve_llm_disagg_stall_ms"] = round(
        statistics.median(stalls) * 1e3, 2
    )
    arm = "off (local prefill)" if no_disagg else "on (kv handoff)"
    print(
        f"serve_llm_disagg_stall_ms: "
        f"{results['serve_llm_disagg_stall_ms']} ms (worst decoder gap "
        f"while a cold long prompt joins the decode engine; disagg {arm})",
        flush=True,
    )

    # Speculative-decoding probe (round 16): decode-bound traffic on one
    # engine — greedy streams, no cache help. ON: a 1-layer draft
    # proposes k=4 per step, the target verifies in one batched forward.
    # Rows: decode tok/s, client-visible per-token p99 gap (burst tokens
    # land together: first pays the step, the rest ~0), accept rate.
    spec_kw = (
        {}
        if no_spec_decode
        else dict(
            spec_decode_tokens=4,
            draft_model_config=GPT2Config.tiny(
                n_layer=1, d_model=128, n_head=4, max_seq=1024
            ),
        )
    )
    eng_s = _Eng(
        LLMConfig(
            model_config=model,
            max_slots=4,
            max_seq=1024,
            prefill_buckets=(32, 128, 1024),
            num_kv_blocks=420,
            enable_prefix_caching=False,
            **spec_kw,
        )
    )
    eng_s.add_request("warm", "warm me", SamplingParams(max_tokens=8))
    while eng_s.has_unfinished():
        eng_s.step()
    eng_s.pop_finished()
    n_tok = 80 if quick else 200
    for i in range(3):
        eng_s.add_request(
            f"sp{i}", f"stream {i}", SamplingParams(max_tokens=n_tok)
        )
    tok0 = eng_s.stats["tokens_generated"]
    token_gaps: list = []
    t0 = time.perf_counter()
    t_last = t0
    while eng_s.has_unfinished():
        before = eng_s.stats["tokens_generated"]
        eng_s.step()
        now = time.perf_counter()
        produced = eng_s.stats["tokens_generated"] - before
        if produced:
            token_gaps.append(now - t_last)
            token_gaps.extend([0.0] * (produced - 1))
        t_last = now
    dt = time.perf_counter() - t0
    eng_s.pop_finished()
    toks = eng_s.stats["tokens_generated"] - tok0
    results["serve_llm_spec_decode_tok_s"] = round(toks / dt, 1)
    results["serve_llm_spec_itl_p99_ms"] = _p99_ms(token_gaps)
    drafted = eng_s.stats["spec_drafted"]
    results["serve_llm_spec_accept_rate"] = round(
        (eng_s.stats["spec_accepted"] / drafted) if drafted else 0.0, 4
    )
    arm = "off (vanilla)" if no_spec_decode else "on (k=4, 1-layer draft)"
    print(
        f"serve_llm_spec_decode: "
        f"{results['serve_llm_spec_decode_tok_s']:,} tok/s, per-token "
        f"p99 {results['serve_llm_spec_itl_p99_ms']} ms, accept rate "
        f"{results['serve_llm_spec_accept_rate']:.1%} [spec {arm}]",
        flush=True,
    )


def _serve_overload_rows(results: dict, no_admission: bool, quick: bool):
    """Overload-protection rows: a seeded flash crowd (tools/traffic_gen)
    fired open-loop at a slow 2-replica deployment whose admission config
    sheds on queue watermarks. The A/B (--no-admission) shows what the
    plane buys: with it, low-priority traffic absorbs the crowd as fast
    429-style rejections and admitted interactive p99 stays bounded;
    without it, every request queues and the whole tail collapses.

      serve_overload_shed_rate            rejected fraction of offered load
      serve_overload_admitted_p99_ttft_ms p99 latency of ADMITTED
                                          interactive requests (the SLO
                                          the plane protects)
      serve_overload_p99_ttft_ms          p99 over every completed request
      serve_overload_{admitted,shed,throttled} router admission counters
    """
    import sys as _sys

    from ray_tpu import serve
    from ray_tpu.core.errors import OverloadedError

    _sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from traffic_gen import schedule, replay  # noqa: E402

    class SlowEcho:
        async def __call__(self, request):
            import asyncio as _a

            await _a.sleep(0.15)
            return {"ok": True}

    dep = serve.deployment(
        SlowEcho,
        name="overload",
        num_replicas=2,
        max_concurrent_queries=8,
        admission_config={
            "queue_high": 5.0,
            "queue_low": 2.0,
            "down_hold_s": 1.0,
            "retry_after_s": 0.2,
        },
    )
    handle = serve.run(dep.bind())
    sched = schedule(
        "flash_crowd",
        seed=7,
        duration_s=6.0 if quick else 12.0,
        base_rps=15.0,
        tenants=4,
        peak_factor=10.0,
    )

    def submit(a):
        t0 = time.perf_counter()
        try:
            handle.options(tenant=a.tenant, priority=a.priority).remote(
                {"body": {"i": a.index}}
            ).result(timeout=120)
            return ("ok", a.priority, time.perf_counter() - t0)
        except OverloadedError:
            return ("rejected", a.priority, time.perf_counter() - t0)

    outcomes = replay(sched, submit, max_workers=96)
    done = [o for o in outcomes if isinstance(o, tuple)]
    rejected = [o for o in done if o[0] == "rejected"]
    ok_interactive = [
        o for o in done if o[0] == "ok" and o[1] == "interactive"
    ]
    results["serve_overload_requests"] = len(sched)
    results["serve_overload_shed_rate"] = round(
        len(rejected) / max(1, len(done)), 4
    )
    results["serve_overload_admitted_p99_ttft_ms"] = _p99_ms(
        [o[2] for o in ok_interactive]
    )
    results["serve_overload_p99_ttft_ms"] = _p99_ms(
        [o[2] for o in done if o[0] == "ok"]
    )
    # Router-side admission counters (the routers run in THIS process).
    from ray_tpu.util.metrics import registry

    for decision in ("admitted", "shed", "throttled"):
        total = 0.0
        for n, tags, v in registry().snapshot()["points"]:
            if (
                n == "raytpu_serve_admission_total"
                and tags.get("decision") == decision
            ):
                total += v
        results[f"serve_overload_{decision}"] = total
    arm = "no-admission" if no_admission else "admission"
    print(
        f"serve_overload [{arm}]: {len(sched)} offered, shed rate "
        f"{results['serve_overload_shed_rate']:.1%}, admitted "
        f"interactive p99 "
        f"{results['serve_overload_admitted_p99_ttft_ms']} ms "
        f"(all-ok p99 {results['serve_overload_p99_ttft_ms']} ms)",
        flush=True,
    )
    serve.shutdown()


def _hist_sum_count(name: str) -> tuple:
    """(sum, count) of one histogram across this process's registry."""
    from ray_tpu.util.metrics import registry

    total, count = 0.0, 0.0
    for n, _tags, v in registry().snapshot()["points"]:
        if n == name and isinstance(v, dict):
            total += v["sum"]
            count += v["count"]
    return total, count


def _counter_total(name: str) -> float:
    from ray_tpu.util.metrics import registry

    total = 0.0
    for n, _tags, v in registry().snapshot()["points"]:
        if n == name:
            total += float(v)
    return total


def _train_rows(results: dict, no_async_dispatch: bool, quick: bool):
    """Host-free train-step rows (PERF.md round-13): a pure-jax
    single-process loop — tiny GPT-2, AOT-compiled donated step — feeding
    DEVICE-RESIDENT metrics through TrainContext.report() with batches
    staged by DevicePrefetchIterator. No cluster runtime: the A/B isolates
    exactly the host work on the step path.

      train_step_overlap          steps/s of the full loop (input + step +
                                  report)
      train_step_host_blocked_ms  host-blocked readback per step
                                  (raytpu_train_host_blocked_seconds
                                  delta / steps). In the OFF arm every
                                  report() waits for the step it just
                                  dispatched AND the loader then runs with
                                  the device idle; in the ON arm the ring
                                  eviction waits on a step dispatched
                                  ``depth`` steps ago while the loader's
                                  cost hides inside that wait
      train_prefetch_misses       staging underruns (consumer beat the
                                  input thread)

    ``--no-async-dispatch`` (= RAY_TPU_TRAIN_ASYNC_DISPATCH=0) is the OFF
    arm and restores the whole synchronous loop: sync readback inside
    every report() AND host-passthrough input (default-depth prefetch
    follows the same kill switch)."""
    import numpy as np

    from ray_tpu.core.config import GLOBAL_CONFIG

    if no_async_dispatch:
        GLOBAL_CONFIG.train_async_dispatch = False

    import jax

    from ray_tpu.models import gpt2
    from ray_tpu.train.context import TrainContext
    from ray_tpu.train.input import DevicePrefetchIterator
    from ray_tpu.train.spmd import (
        compile_train_step,
        default_optimizer,
        make_train_state,
        make_train_step,
    )

    cfg = gpt2.GPT2Config.tiny(n_layer=2, d_model=128, max_seq=128)
    steps = 40 if quick else 120
    B = 8
    opt = default_optimizer(total_steps=steps)
    state = make_train_state(
        lambda k: gpt2.init_params(k, cfg), opt, jax.random.key(0)
    )
    # donate_batch stays off: int32 token buffers have no dtype-matching
    # outputs to reuse, so donation would only emit XLA's unusable-donation
    # warning. donate_state off too: the CPU runtime blocks the dispatch
    # call until a donated input is defined (~the full step time), which
    # would hide the readback stall this A/B exists to measure (TPU
    # resolves aliasing asynchronously — bench.py keeps donation on).
    step = make_train_step(
        lambda p, b: gpt2.loss_fn(p, b, cfg), opt, donate_state=False
    )
    rng = np.random.default_rng(0)

    def host_batches():
        # Synthetic loader with REAL host cost per batch (~20-25 ms on
        # this box vs a ~55 ms step): an oversampled byte "corpus" folded
        # into vocab ids, standing in for tokenize+pack. This is the work
        # the overlap tier takes off the step path — the prefetch thread
        # absorbs it in the ON arm; the OFF arm (passthrough) pays it
        # inline between steps while the device sits idle.
        for _ in range(steps):
            raw = rng.integers(
                0, 256, size=(B * cfg.max_seq, 2048), dtype=np.int64
            )
            tokens = (
                (raw.cumsum(axis=1).sum(axis=1) % cfg.vocab_size)
                .astype(np.int32)
                .reshape(B, cfg.max_seq)
            )
            yield {"tokens": tokens, "targets": np.roll(tokens, -1, axis=1)}

    # AOT-compile against a staged example OUTSIDE the timed loop. lower()
    # only traces — donation happens when the executable runs — so the
    # example batch stays valid.
    example = jax.device_put(next(iter(host_batches())))
    compiled, _flops = compile_train_step(step, state, example)

    ctx = TrainContext(
        experiment_name="ray_perf",
        world_size=1,
        world_rank=0,
        local_rank=0,
        local_world_size=1,
        node_rank=0,
    )
    blocked0, _ = _hist_sum_count("raytpu_train_host_blocked_seconds")
    misses0 = _counter_total("raytpu_train_prefetch_misses_total")
    it = DevicePrefetchIterator(host_batches())
    input_wait = 0.0  # consumer-thread time spent obtaining the next batch
    t0 = time.perf_counter()
    while True:
        t_in = time.perf_counter()
        try:
            batch = next(it)
        except StopIteration:
            break
        input_wait += time.perf_counter() - t_in
        state, metrics = compiled(state, batch)
        ctx.report(metrics)
    ctx.flush()
    jax.block_until_ready(state["step"])
    dt = time.perf_counter() - t0
    blocked1, _ = _hist_sum_count("raytpu_train_host_blocked_seconds")
    reports = ctx.drain_reports()
    assert len(reports) == steps, (len(reports), steps)

    results["train_step_overlap"] = round(steps / dt, 2)
    # Host-blocked = everything the consumer thread did per step that was
    # NOT dispatching: metric readback stalls (the histogram) + obtaining
    # the next batch (inline loader+h2d in the OFF arm; a queue pop —
    # usually instant — in the ON arm). The tier's whole point is driving
    # this toward pure device-wait while steps/s rises.
    results["train_step_host_blocked_ms"] = round(
        ((blocked1 - blocked0) + input_wait) * 1e3 / steps, 4
    )
    results["train_prefetch_misses"] = (
        _counter_total("raytpu_train_prefetch_misses_total") - misses0
    )
    arm = "off (sync readback)" if no_async_dispatch else (
        f"on (depth {GLOBAL_CONFIG.train_async_dispatch_depth})"
    )
    print(
        f"train_step_overlap: {results['train_step_overlap']:,.1f} steps/s, "
        f"host-blocked {results['train_step_host_blocked_ms']:.3f} ms/step, "
        f"{results['train_prefetch_misses']:.0f} prefetch misses "
        f"[async dispatch {arm}]",
        flush=True,
    )


def _elastic_train_fn(config):
    """Worker loop for the elastic-recovery probe: deterministic
    replicated numpy state retained via ``elastic_state=`` every step,
    plus a checkpoint round every ``ckpt_every`` steps so the
    ``--no-elastic`` arm has something to restore from. Module-level so
    worker processes can unpickle it."""
    import os as _os
    import tempfile as _tmp
    import time as _t

    import numpy as _np

    import ray_tpu.train as train

    ctx = train.get_context()
    el = train.get_elastic_state()
    if el is not None:
        # Live re-formation: resume from the peer-resharded state — no
        # checkpoint-storage read on this path.
        state = _np.asarray(el["state"])
        start = int(el["index"]) + 1
    else:
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            with ckpt.as_directory() as d:
                state = _np.load(_os.path.join(d, "state.npy"))
            start = int(state[1]) + 1
        else:
            state = _np.zeros(2)
            start = 0
    for step in range(start, int(config["steps"])):
        state = state + _np.asarray([1.0, 0.0])
        state[1] = float(step)
        if (
            step % int(config.get("ckpt_every", 5)) == 0
            and ctx.get_world_rank() == 0
        ):
            with _tmp.TemporaryDirectory() as d:
                _np.save(_os.path.join(d, "state.npy"), state)
                train.report(
                    {"step": step},
                    checkpoint=train.Checkpoint(d),
                    elastic_state=state,
                )
        else:
            train.report({"step": step}, elastic_state=state)
        _t.sleep(float(config.get("step_s", 0.05)))


def _train_elastic_rows(results: dict, no_elastic: bool, quick: bool):
    """Elastic-recovery probe (round-21 robustness A/B): a 2-node
    in-process cluster runs a 2-worker gang whose train fn retains
    ``elastic_state=`` every step; mid-run the second node gets a
    graceful drain notice (the preemption lifecycle). The ON arm pauses
    the survivor at its next step boundary, reshards state peer-to-peer,
    and resumes at world size 1 in the SAME generation; the OFF arm
    (``--no-elastic`` = RAY_TPU_ELASTIC_TRAIN=0) tears the gang down and
    rebuilds from the latest checkpoint. Both arms stamp the SAME
    interval — drain notice observed -> first post-recovery report — so
    the row is directly comparable:

      train_elastic_recovery_ms   drain seen -> first report after
                                  recovery
      train_elastic_reshapes      raytpu_train_reshapes_total delta
                                  (1 shrink in the ON arm, 0 in OFF)
      train_elastic_end_world     raytpu_train_world_size after the run
                                  (1 = re-formed smaller; 2 = rebuilt at
                                  full size from the checkpoint)
    """
    import tempfile
    import threading

    import ray_tpu
    from ray_tpu.core.config import GLOBAL_CONFIG
    from ray_tpu.train import elastic as train_elastic
    from ray_tpu.train import FailureConfig, RunConfig, ScalingConfig
    from ray_tpu.train.backend import BackendConfig
    from ray_tpu.train.controller import TrainController

    GLOBAL_CONFIG.elastic_train = not no_elastic
    GLOBAL_CONFIG.elastic_grow_check_s = 0.0  # probe measures the shrink
    GLOBAL_CONFIG.drain_grace_s = 30.0

    runtime = ray_tpu.init(num_cpus=2)
    node2 = runtime.add_node({"CPU": 1.0})
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        v = runtime.head.cluster_view.get(node2.node_id)
        if v is not None and v.alive:
            break
        time.sleep(0.1)
    else:
        raise TimeoutError("second node never joined the head's view")

    steps = 60 if quick else 120
    storage = tempfile.mkdtemp(prefix="raytpu_elastic_perf_")
    controller = TrainController(
        _elastic_train_fn,
        {"steps": steps, "ckpt_every": 5, "step_s": 0.05},
        ScalingConfig(
            num_workers=2,
            resources_per_worker={"CPU": 1},
            # SPREAD (soft): one worker per node while both nodes live,
            # and the --no-elastic rebuild can still pack both workers
            # onto the survivor after the drained node dies.
            placement_strategy="SPREAD",
        ),
        RunConfig(
            name="elastic_probe",
            storage_path=storage,
            # Zero failure budget: BOTH recovery paths classify the drain
            # as "preempted" and must not burn max_failures.
            failure_config=FailureConfig(max_failures=0),
        ),
        BackendConfig(),
    )
    reshapes0 = _counter_total("raytpu_train_reshapes_total")
    box: dict = {}

    def _fit():
        box["result"] = controller.run()

    th = threading.Thread(target=_fit, daemon=True)
    th.start()
    # Drain only once the gang is actually running with a rank on node2 —
    # a notice during SCHEDULING would just steer placement off the node
    # and measure nothing.
    deadline = time.monotonic() + 120.0
    while time.monotonic() < deadline:
        grp = controller._active_group
        if (
            controller.state == "RUNNING"
            and grp is not None
            and any(
                w.metadata["node_id"] == node2.node_id for w in grp.workers
            )
        ):
            break
        time.sleep(0.1)
    else:
        raise TimeoutError("gang never started with a rank on node2")
    time.sleep(0.5)  # a few steps of progress (and a checkpoint round)
    ray_tpu.drain_node(node2.node_id, grace_s=30.0, reason="preempted")
    th.join(timeout=180)
    result = box.get("result")
    if result is None or result.error is not None:
        raise RuntimeError(
            f"elastic probe run did not finish cleanly: "
            f"{getattr(result, 'error', 'run() still going')}"
        )

    rec_ms = train_elastic.last_recovery_ms()
    results["train_elastic_recovery_ms"] = (
        round(rec_ms, 1) if rec_ms is not None else None
    )
    results["train_elastic_reshapes"] = (
        _counter_total("raytpu_train_reshapes_total") - reshapes0
    )
    results["train_elastic_end_world"] = _counter_total(
        "raytpu_train_world_size"
    )
    arm = (
        "off (checkpoint rebuild)"
        if no_elastic
        else "on (live re-formation)"
    )
    print(
        f"train_elastic_recovery_ms: {results['train_elastic_recovery_ms']}"
        f" ms, {results['train_elastic_reshapes']:.0f} reshapes, end world "
        f"{results['train_elastic_end_world']:.0f} [elastic {arm}]",
        flush=True,
    )
    ray_tpu.shutdown()


def _podracer_env_maker():
    """CartPole with a ~0.25 ms per-env-step cost emulating a non-trivial
    simulator (a raw CartPole step is ~1 µs — three orders of magnitude
    under any production env, which would make ANY acting-plane design
    look control-plane-bound). Module-level so worker processes can
    unpickle it."""
    import time as _t

    import gymnasium as gym

    class _SlowStep(gym.Wrapper):
        def step(self, action):
            _t.sleep(0.00025)
            return self.env.step(action)

    return _SlowStep(gym.make("CartPole-v1"))


def _rl_rows(results: dict, no_podracer: bool, quick: bool):
    """Podracer RL rows: one fixed-budget DQN run on the emulated-cost
    CartPole (see _podracer_env_maker), decoupled planes ON (HEAD
    defaults) vs the --no-podracer kill switch (the single-loop
    sample→update iteration, byte-identical to DQN). Rows:

      rl_env_steps_per_s        acting-plane throughput — the headline
      rl_learner_updates_per_s  grad steps/s landed alongside the acting
      rl_weight_lag_p99         p99 published-vs-applied version lag
                                (bounded by podracer_staleness_steps;
                                identically 0 on the lockstep arm)
      rl_inference_batch_mean   coalesced rows per inference forward
                                (decoupled arm only)
    """
    from ray_tpu.rllib import PodracerConfig

    target = 4000 if quick else 12000
    arm = "single-loop" if no_podracer else "podracer"
    config = PodracerConfig(
        num_env_runners=2,
        num_envs_per_env_runner=16,
        rollout_fragment_length=16,
        lr=1e-3,
        hidden=(128, 128),
        seed=0,
        epsilon_anneal_steps=4 * target,
        learning_starts=512,
        train_batch_size=256,
        num_train_batches_per_iteration=16,
        target_network_update_freq=200,
        podracer_staleness_steps=2,
        trajectory_queue_depth=8,
        inference_batch_window_s=0.001,
        inference_max_batch=64,
    ).environment(_podracer_env_maker)
    algo = config.build()
    # Warm the jitted paths out of the measured window (both arms pay
    # their compiles here). The warmup must run PAST learning_starts so
    # the learner's update/scatter programs compile now, not inside the
    # measured window.
    algo.run(1_536, time_budget_s=180)
    t0 = time.perf_counter()
    out = algo.run(target, time_budget_s=300 if quick else 600)
    dt = time.perf_counter() - t0
    results["rl_env_steps_per_s"] = round(out["env_steps"] / dt, 1)
    results["rl_learner_updates_per_s"] = round(
        out["grad_updates"] / dt, 2
    )
    results["rl_weight_lag_p99"] = round(out["weight_lag_p99"], 2)
    infer = out.get("inference") or {}
    if infer.get("batches"):
        results["rl_inference_batch_mean"] = round(
            infer["rows"] / infer["batches"], 2
        )
    results["rl_restarts"] = out.get("restarts", 0)
    results["rl_queue_drops"] = out.get("queue_drops", 0)
    print(
        f"rl [{arm}]: {results['rl_env_steps_per_s']:,.0f} env_steps/s, "
        f"{results['rl_learner_updates_per_s']:,.1f} updates/s, "
        f"weight-lag p99 {results['rl_weight_lag_p99']}",
        flush=True,
    )
    algo.stop()


def _data_rows(results: dict, quick: bool) -> None:
    """Governed out-of-core data-pipeline rows (round-18 memory-governed
    streaming data plane): the object store is capped WELL below the
    dataset size, a map pipeline streams ~4x the cap through
    iter_batches, and the rows report throughput + how the store
    behaved. The caller shrank GLOBAL_CONFIG.object_store_bytes BEFORE
    init (capacity is fixed at store creation) and flipped
    data_governor for the --no-data-governor arm."""
    import threading

    import ray_tpu.data as rd
    from ray_tpu.core.config import GLOBAL_CONFIG

    cap = GLOBAL_CONFIG.object_store_bytes
    n_blocks = 16 if quick else 32
    rows_per_block = 128
    # ~8 MB/block: 1024 float64 payload lanes per row.
    lanes = 8 * 1024 * 1024 // (rows_per_block * 8)

    peak = [0]
    spills = [0]
    stop = [False]

    def poll():
        while not stop[0]:
            used = sp = 0
            for n in ray_tpu.nodes():
                st = n.get("StoreStats") or {}
                used += int(st.get("used_bytes", 0))
                sp += int(st.get("spills", 0))
            peak[0] = max(peak[0], used)
            spills[0] = sp
            time.sleep(0.025)

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    payload = lambda b: {  # noqa: E731 — shipped by value to workers
        "id": b["id"],
        "x": np.ones((len(b["id"]), lanes), np.float64),
    }
    ds = rd.range(n_blocks * rows_per_block, parallelism=n_blocks)
    ds = ds.map_batches(payload)
    t0 = time.perf_counter()
    rows = 0
    for batch in ds.iter_batches(batch_size=rows_per_block):
        rows += len(batch["id"])
    dt = time.perf_counter() - t0
    stop[0] = True
    poller.join()
    results["data_pipeline_rows_per_s"] = round(rows / dt, 1)
    results["data_peak_store_frac"] = round(peak[0] / cap, 3)
    results["data_store_spills"] = spills[0]
    gov = ds.governor_stats()
    results["data_throttle_events"] = (
        0 if gov is None else gov["throttle_events"]
    )
    print(
        f"data_pipeline [{'governed' if gov is not None else 'kill-switch'}]"
        f": {results['data_pipeline_rows_per_s']:,.0f} rows/s, peak store "
        f"{results['data_peak_store_frac']:.0%} of cap, "
        f"{results['data_store_spills']} spills, "
        f"{results['data_throttle_events']} throttles",
        flush=True,
    )


def _pctl_ms(sorted_ms: list, q: float) -> float:
    if not sorted_ms:
        return 0.0
    return round(sorted_ms[min(len(sorted_ms) - 1, int(q * len(sorted_ms)))], 4)


def _fleet_rows(results: dict, quick: bool) -> None:
    """Fleet-scale control-plane rows (round-19): the in-process fleet
    emulator (core/fleet_emu.py) drives the REAL GCS wire handlers at
    100/500/1,000 emulated nodes from one seeded lease schedule and
    reports exact per-pick placement latency (read off
    ``gcs.place_latency_ms`` — no RPC overhead in the number), heartbeat
    RPC cost, and view-delta wire size per changed node. No cluster
    runtime: the GCS + one shared host endpoint is the whole process
    tree. The ``--no-sched-index`` arm re-runs the SAME tape through the
    original full-scan ``pick_node`` (tools/ab_fleet.py and bench.py's
    fleet_scale record ride this pair)."""
    from ray_tpu.core.config import GLOBAL_CONFIG
    from ray_tpu.core.fleet_emu import FleetEmulator, schedule_events

    ops = 150 if quick else GLOBAL_CONFIG.fleet_emu_lease_ops
    seed = 19
    arm = "index" if GLOBAL_CONFIG.sched_index else "scan"
    for n in (100, 500, 1000):
        tape = schedule_events(seed, "steady", n, ops)
        with FleetEmulator(n, seed=seed) as emu:
            emu.register_all()
            # Registration pre-populates the latency deque with nothing
            # (no picks yet); every sample below is a real placement.
            emu.run_schedule(tape)
            lat = sorted(emu.place_latencies_ms())
            results[f"fleet_place_p50_ms_{n}"] = _pctl_ms(lat, 0.50)
            results[f"fleet_place_p99_ms_{n}"] = _pctl_ms(lat, 0.99)
            results[f"fleet_decision_digest_{n}"] = emu.decision_digest()
            if n == 1000:
                results["fleet_hb_ingest_us"] = round(
                    emu.heartbeat_burst_us(200 if quick else 500), 1
                )
                cursor = emu.delta_probe(-1)["version"]
                live = [e for e in emu.emu_nodes.values() if e.alive]
                for e in live[:50]:
                    e.available = dict(e.available)
                    e.available["CPU"] = max(
                        0.0, e.available.get("CPU", 0.0) - 0.5
                    )
                    emu.heartbeat(e)
                probe = emu.delta_probe(cursor)
                results["fleet_delta_bytes_per_node"] = round(
                    probe["bytes"] / max(1, probe["changed"]), 1
                )
                results["fleet_delta_nodes"] = probe["changed"]
            print(
                f"fleet_scale [{arm}] {n} nodes: place p50 "
                f"{results[f'fleet_place_p50_ms_{n}']} ms, p99 "
                f"{results[f'fleet_place_p99_ms_{n}']} ms "
                f"({len(lat)} picks)",
                flush=True,
            )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--no-coalesce",
        action="store_true",
        help="kill switch: one-write-per-frame transport, unbatched "
        "lease/submission paths (the A/B baseline for PERF.md round-6)",
    )
    ap.add_argument(
        "--no-metrics",
        action="store_true",
        help="kill switch: disable all runtime telemetry (equivalent to "
        "RAY_TPU_METRICS_ENABLED=0) — the A/B baseline proving the "
        "instrumentation tax stays within the 5%% budget",
    )
    ap.add_argument(
        "--no-scatter-gather",
        action="store_true",
        help="kill switch: in-band frame pickling + join-based flush "
        "(the A/B baseline for the PERF.md round-8 data plane)",
    )
    ap.add_argument(
        "--data-plane-only",
        action="store_true",
        help="run only the large-object rows (bench.py rides this for "
        "the BENCH_r* data-plane record)",
    )
    ap.add_argument(
        "--no-hierarchical",
        action="store_true",
        help="kill switch: flat one-ring collectives (equivalent to "
        "RAY_TPU_HIERARCHICAL_COLLECTIVES=0) — the A/B baseline for the "
        "PERF.md round-11 hierarchical-collective tier",
    )
    ap.add_argument(
        "--no-quantized",
        action="store_true",
        help="keep the hierarchical structure but ship the DCN leg at "
        "full precision (no block-int8 codec) — isolates the "
        "quantization arm of the round-11 A/B",
    )
    ap.add_argument(
        "--serve-llm-only",
        action="store_true",
        help="run only the LLM-serving rows (2 tiny-model replicas on "
        "CPU jax, streaming clients): serve_llm_shared_prefix / "
        "serve_llm_mixed_len tok/s + p99 TTFT/ITL — the round-12 "
        "cache-aware-serving A/B rides this via tools/ab_prefix_routing.py",
    )
    ap.add_argument(
        "--no-prefix-routing",
        action="store_true",
        help="kill switch: cache-blind router (equivalent to "
        "RAY_TPU_PREFIX_ROUTING=0) — the A/B baseline for prefix-affinity "
        "routing (PERF.md round-12)",
    )
    ap.add_argument(
        "--no-chunked-prefill",
        action="store_true",
        help="serve-llm rows only: engines admit with whole-suffix "
        "prefill (prefill_chunk_tokens=0) — the A/B baseline for chunked "
        "prefill (PERF.md round-12)",
    )
    ap.add_argument(
        "--no-disagg",
        action="store_true",
        help="kill switch: unified serving — the disagg stall probe's "
        "long prompts prefill LOCALLY on the decode engine (equivalent "
        "to RAY_TPU_DISAGG=0; the A/B baseline for the round-16 "
        "prefill/decode split)",
    )
    ap.add_argument(
        "--no-spec-decode",
        action="store_true",
        help="kill switch: vanilla one-token decode on the spec probe "
        "(equivalent to RAY_TPU_SPEC_DECODE=0; the A/B baseline for "
        "round-16 speculative decoding)",
    )
    ap.add_argument(
        "--serve-overload",
        action="store_true",
        help="run only the overload-protection rows (seeded flash crowd "
        "from tools/traffic_gen.py against a slow 2-replica deployment): "
        "serve_overload_shed_rate + admitted-interactive p99 — the "
        "admission A/B rides this via tools/ab_admission.py and "
        "bench.py's serve_overload record",
    )
    ap.add_argument(
        "--no-admission",
        action="store_true",
        help="kill switch: no admission control, priority shedding, or "
        "bounded replica queues (equivalent to RAY_TPU_ADMISSION=0) — "
        "the A/B baseline for the overload-protection tier",
    )
    ap.add_argument(
        "--train-only",
        action="store_true",
        help="run only the host-free train-step rows (pure-jax CPU loop, "
        "no cluster): train_step_overlap steps/s + host-blocked ms/step — "
        "the round-13 async-dispatch A/B rides this via "
        "tools/ab_train_overlap.py and bench.py's train_overlap record",
    )
    ap.add_argument(
        "--no-async-dispatch",
        action="store_true",
        help="kill switch: synchronous train loop — device->host metric "
        "readback inside every report() (equivalent to "
        "RAY_TPU_TRAIN_ASYNC_DISPATCH=0) — the A/B baseline for the "
        "round-13 host-free train steps",
    )
    ap.add_argument(
        "--elastic-probe",
        action="store_true",
        help="with --train-only: run the elastic-recovery row instead "
        "(2-node in-process cluster, 2-worker gang, graceful drain "
        "notice mid-run): train_elastic_recovery_ms = drain seen -> "
        "first report after recovery — the round-21 robustness A/B "
        "rides this via bench.py's train_elastic record",
    )
    ap.add_argument(
        "--no-elastic",
        action="store_true",
        help="kill switch: membership changes tear the gang down and "
        "rebuild from the latest checkpoint (equivalent to "
        "RAY_TPU_ELASTIC_TRAIN=0) — the A/B baseline for the round-21 "
        "elastic live re-formation",
    )
    ap.add_argument(
        "--rl-only",
        action="store_true",
        help="run only the podracer RL rows (decoupled DQN on an "
        "emulated-cost CartPole): rl_env_steps_per_s + learner updates/s "
        "+ weight-lag p99 — the round-17 A/B rides this via "
        "tools/ab_podracer.py and bench.py's podracer record",
    )
    ap.add_argument(
        "--no-podracer",
        action="store_true",
        help="kill switch: single-loop sample→update DQN iteration "
        "(equivalent to RAY_TPU_PODRACER=0; the A/B baseline for the "
        "round-17 decoupled actor/inference/learner planes)",
    )
    ap.add_argument(
        "--data-only",
        action="store_true",
        help="run only the governed out-of-core data-pipeline rows "
        "(object store capped ~4x below the dataset): rows/s + peak "
        "store occupancy + spills — the round-18 memory-governor A/B "
        "rides this via tools/ab_data_governor.py and bench.py's "
        "data_governor record",
    )
    ap.add_argument(
        "--no-data-governor",
        action="store_true",
        help="kill switch: ungoverned streaming executor (equivalent to "
        "RAY_TPU_DATA_GOVERNOR=0) — the A/B baseline for the round-18 "
        "memory-governed data plane; on the --data-only workload this "
        "arm spills where the governed arm stays under the watermark",
    )
    ap.add_argument(
        "--fleet-only",
        action="store_true",
        help="run only the fleet-scale control-plane rows (in-process "
        "fleet emulator at 100/500/1,000 emulated nodes driving the real "
        "GCS handlers, no cluster runtime): placement p50/p99 per scale, "
        "heartbeat RPC µs/msg, view-delta bytes/node — the round-19 "
        "scheduler-index A/B rides this via tools/ab_fleet.py and "
        "bench.py's fleet_scale record",
    )
    ap.add_argument(
        "--no-sched-index",
        action="store_true",
        help="kill switch: every placement decision takes the original "
        "full-scan pick_node path (equivalent to RAY_TPU_SCHED_INDEX=0) "
        "— the A/B baseline for the round-19 feasibility-indexed "
        "scheduler",
    )
    ap.add_argument(
        "--no-flightrec",
        action="store_true",
        help="kill switch: no flight-recorder phase events anywhere "
        "(equivalent to RAY_TPU_FLIGHTREC=0) — the A/B baseline for the "
        "observability plane (PERF.md gives the recorder's cost as "
        "measured on the chip's host)",
    )
    ap.add_argument(
        "--faults",
        metavar="SEED:SPEC",
        help="enable the fault-injection plane for the whole run "
        "(RAY_TPU_FAULTS syntax; includes the node.preempt rule — a "
        "seeded graceful-drain notice) — the chaos-overhead arm of the "
        "robustness A/B; the default arm (injector off) must stay "
        "within noise of the pre-robustness numbers",
    )
    args = ap.parse_args()
    if args.faults:
        from ray_tpu.core import faults as _faults

        # Spawned worker processes re-import faults and read the env var;
        # without this, worker-side fault sites silently never fire.
        os.environ["RAY_TPU_FAULTS"] = args.faults
        _faults.install(_faults.parse_env(args.faults))
    batch = 20 if args.quick else 100
    min_s = 0.5 if args.quick else 2.0

    if args.train_only:
        # Pure-jax in-process rows: no cluster runtime, both cores to the
        # jitted step. CPU jax even where a TPU plugin is installed.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        results = {}
        if args.elastic_probe:
            _train_elastic_rows(
                results, no_elastic=args.no_elastic, quick=args.quick
            )
        else:
            _train_rows(
                results,
                no_async_dispatch=args.no_async_dispatch,
                quick=args.quick,
            )
        print(json.dumps(results), flush=True)
        return 0

    if (
        args.no_coalesce
        or args.no_metrics
        or args.no_scatter_gather
        or args.no_hierarchical
        or args.no_quantized
        or args.no_prefix_routing
        or args.no_admission
        or args.no_disagg
        or args.no_spec_decode
        or args.no_podracer
        or args.no_data_governor
        or args.no_sched_index
        or args.no_flightrec
    ):
        from ray_tpu.core.config import GLOBAL_CONFIG

        # Before init: the head ships this config to every node/worker.
        if args.no_coalesce:
            GLOBAL_CONFIG.rpc_coalesce_enabled = False
        if args.no_metrics:
            GLOBAL_CONFIG.metrics_enabled = False
        if args.no_scatter_gather:
            GLOBAL_CONFIG.rpc_scatter_gather_enabled = False
        if args.no_hierarchical:
            GLOBAL_CONFIG.hierarchical_collectives = False
        if args.no_quantized:
            GLOBAL_CONFIG.collective_quantize_dcn = False
        if args.no_prefix_routing:
            GLOBAL_CONFIG.prefix_routing = False
        if args.no_admission:
            GLOBAL_CONFIG.admission = False
        if args.no_disagg:
            GLOBAL_CONFIG.disagg = False
        if args.no_spec_decode:
            GLOBAL_CONFIG.spec_decode = False
        if args.no_podracer:
            GLOBAL_CONFIG.podracer = False
        if args.no_data_governor:
            GLOBAL_CONFIG.data_governor = False
        if args.no_sched_index:
            GLOBAL_CONFIG.sched_index = False
        if args.no_flightrec:
            GLOBAL_CONFIG.flightrec = False

    if args.fleet_only:
        # In-process emulator rows: no cluster runtime at all (the GCS +
        # one shared host endpoint IS the process tree).
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        results = {}
        _fleet_rows(results, quick=args.quick)
        print(json.dumps(results), flush=True)
        return 0

    if args.data_only:
        # The store must be capped BEFORE init (capacity is fixed at
        # store creation): 4x below the dataset the rows stream through.
        from ray_tpu.core.config import GLOBAL_CONFIG as _DCFG

        _DCFG.object_store_bytes = 32 * 1024 * 1024
        ray_tpu.init(num_cpus=4)
        results = {}
        _data_rows(results, quick=args.quick)
        print(json.dumps(results), flush=True)
        ray_tpu.shutdown()
        return 0

    if args.rl_only:
        # Runner/learner jax stays on CPU even where a TPU plugin is
        # installed: workers inherit the driver env.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    if args.serve_llm_only:
        # Replica actors must run CPU jax even where a TPU plugin is
        # installed: workers inherit the driver env.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    ray_tpu.init(num_cpus=16)
    results = {}

    if args.serve_llm_only:
        _serve_llm_rows(
            results,
            no_chunked_prefill=args.no_chunked_prefill,
            quick=args.quick,
            no_disagg=args.no_disagg,
            no_spec_decode=args.no_spec_decode,
        )
        print(json.dumps(results), flush=True)
        ray_tpu.shutdown()
        return 0

    if args.serve_overload:
        _serve_overload_rows(
            results, no_admission=args.no_admission, quick=args.quick
        )
        print(json.dumps(results), flush=True)
        ray_tpu.shutdown()
        return 0

    if args.rl_only:
        _rl_rows(results, no_podracer=args.no_podracer, quick=args.quick)
        print(json.dumps(results), flush=True)
        ray_tpu.shutdown()
        return 0

    def record(name, fn, multiplier=1):
        n, rate = timeit(name, fn, multiplier, min_s=min_s)
        results[n] = rate

    # -- large objects (round-8 data plane) ----------------------------------
    # put_large: driver put through the shm single-copy path. get_large:
    # a BORROWER (actor-side) get of a driver-owned inline object — the
    # leg where the value actually rides RPC frames, so the scatter-gather
    # A/B shows here. actor_array_args: multi-MB array args on pipelined
    # actor calls (args always ride the push frame, at any size).
    from ray_tpu.core.config import GLOBAL_CONFIG as _CFG

    large = np.zeros(8 * 1024 * 1024, dtype=np.uint8)  # 8 MB
    mb = large.nbytes / 1e6

    def put_large():
        ref = ray_tpu.put(large)
        del ref

    n, rate = timeit("put_large", put_large, 1, min_s=min_s, max_iters=30)
    results[n] = round(rate * mb, 2)
    print(f"  -> {results[n]:.1f} MB/s", flush=True)

    @ray_tpu.remote
    class _DataSink:
        def checksum(self, x):
            return int(x[0]) + int(x[-1])

        def fetch(self, ref):
            return int(ray_tpu.get(ref[0])[0])

    dsink = _DataSink.remote()
    ray_tpu.get(dsink.checksum.remote(np.zeros(8, dtype=np.uint8)))

    # Owner-side inline storage for the borrower-get row: bump the inline
    # cap (driver-side decision only) so the 8 MB value is served from the
    # owner's memory store over RPC instead of the shm file plane.
    old_inline = _CFG.max_inline_object_bytes
    _CFG.max_inline_object_bytes = large.nbytes + 1
    try:
        inline_ref = ray_tpu.put(large)
    finally:
        _CFG.max_inline_object_bytes = old_inline

    def get_large():
        ray_tpu.get(dsink.fetch.remote([inline_ref]))

    n, rate = timeit("get_large", get_large, 1, min_s=min_s, max_iters=30)
    results[n] = round(rate * mb, 2)
    print(f"  -> {results[n]:.1f} MB/s", flush=True)

    def actor_array_args():
        ray_tpu.get(
            [dsink.checksum.remote(large) for _ in range(4)]
        )

    n, rate = timeit(
        "actor_array_args", actor_array_args, 4, min_s=min_s, max_iters=20
    )
    results[n] = round(rate * mb, 2)
    print(f"  -> {results[n]:.1f} MB/s", flush=True)

    if args.data_plane_only:
        print(json.dumps(results), flush=True)
        ray_tpu.shutdown()
        return 0

    # -- objects -------------------------------------------------------------
    small = b"x" * 1024

    def put_small():
        for _ in range(batch):
            ray_tpu.put(small)

    record("single_client_put_calls_1kb", put_small, batch)

    ref_small = ray_tpu.put(small)

    def get_small():
        for _ in range(batch):
            ray_tpu.get(ref_small)

    record("single_client_get_calls_1kb", get_small, batch)

    big = np.zeros(64 * 1024 * 1024, dtype=np.uint8)  # 64 MB through shm

    def put_big():
        ref = ray_tpu.put(big)
        del ref

    n, rate = timeit(
        "single_client_put_gigabytes", put_big, 1, min_s=min_s, max_iters=20
    )
    results[n] = rate * big.nbytes / 1e9
    print(f"  -> {results[n]:.2f} GB/s", flush=True)

    # -- tasks ---------------------------------------------------------------
    def tasks_sync():
        for _ in range(batch):
            ray_tpu.get(tiny.remote())

    record("single_client_tasks_sync", tasks_sync, batch)

    def tasks_async():
        ray_tpu.get([tiny.remote() for _ in range(batch * 5)])

    record("single_client_tasks_async", tasks_async, batch * 5)

    # -- actors --------------------------------------------------------------
    sink = Sink.remote()
    ray_tpu.get(sink.ping.remote())

    def actor_sync():
        for _ in range(batch):
            ray_tpu.get(sink.ping.remote())

    record("1_1_actor_calls_sync", actor_sync, batch)

    def actor_async():
        ray_tpu.get([sink.ping.remote() for _ in range(batch * 5)])

    record("1_1_actor_calls_async", actor_async, batch * 5)

    def actor_with_arg():
        ray_tpu.get([sink.with_arg.remote(small) for _ in range(batch * 2)])

    record("1_1_actor_calls_with_arg_async", actor_with_arg, batch * 2)

    asink = Sink.options(max_concurrency=8).remote()
    ray_tpu.get(asink.aping.remote())

    def async_actor_async():
        ray_tpu.get([asink.aping.remote() for _ in range(batch * 5)])

    record("1_1_async_actor_calls_async", async_actor_async, batch * 5)

    # n:n — 4 actors, submissions interleaved from one driver (our driver is
    # one process; the reference uses n driver processes).
    sinks = [Sink.remote() for _ in range(4)]
    ray_tpu.get([s.ping.remote() for s in sinks])

    def n_n_async():
        refs = []
        for _ in range(batch * 2):
            for s in sinks:
                refs.append(s.ping.remote())
        ray_tpu.get(refs)

    record("n_n_actor_calls_async", n_n_async, batch * 2 * len(sinks))

    # -- collectives (round-11 hierarchical + quantized DCN) -----------------
    # Two allreduce rows over real member-actor gangs on the coordinator
    # data plane: a 2-slice group (slice identities passed explicitly, so
    # auto strategy picks hierarchical unless --no-hierarchical) and a
    # 1-slice group (always flat — the parity row: hierarchical selection
    # must not touch it). Bytes ride MB/s like the data-plane rows; the
    # dcn byte counters from rank 0's process give the quantization ratio.

    @ray_tpu.remote(num_cpus=0)
    class _CollMember:
        def __init__(self, world, rank, group, slice_name):
            from ray_tpu.util import collective as col

            self._col = col
            self._group = group
            self._comm = col.init_collective_group(
                world, rank, backend="cpu", group_name=group,
                timeout_s=120.0, slice_name=slice_name,
            )

        def strategy(self):
            return self._comm.backend

        def allreduce(self, n):
            t = np.ones(n, np.float32)
            out = self._col.allreduce(t, group_name=self._group)
            return float(np.asarray(out)[0])

        def dcn_bytes(self):
            from ray_tpu.util.metrics import registry

            out = {"pre": 0.0, "post": 0.0}
            for name, _tags, value in registry().snapshot()["points"]:
                if name == "raytpu_collective_dcn_bytes_pre_total":
                    out["pre"] = float(value)
                elif name == "raytpu_collective_dcn_bytes_post_total":
                    out["post"] = float(value)
            return out

        def destroy(self):
            from ray_tpu.util import collective as col

            col.destroy_collective_group(self._group)
            return True

    n_elems = 256 * 1024  # 1 MiB fp32 per rank per op
    coll_mb = n_elems * 4 / 1e6
    world = 4
    for row, slices in (
        ("collective_allreduce_2slice", ["s0", "s0", "s1", "s1"]),
        ("collective_allreduce_1slice", ["s0", "s0", "s0", "s0"]),
    ):
        members = [
            _CollMember.remote(world, r, row, slices[r])
            for r in range(world)
        ]
        strat = ray_tpu.get(
            [m.strategy.remote() for m in members], timeout=120
        )[0]

        def coll_op(ms=members):
            ray_tpu.get(
                [m.allreduce.remote(n_elems) for m in ms], timeout=120
            )

        n, rate = timeit(row, coll_op, 1, min_s=min_s, max_iters=30)
        results[n] = round(rate * coll_mb, 2)
        print(f"  -> {results[n]:.1f} MB/s ({strat})", flush=True)
        if row == "collective_allreduce_2slice":
            b = ray_tpu.get(members[0].dcn_bytes.remote(), timeout=60)
            if b["post"]:
                results["collective_dcn_bytes_ratio"] = round(
                    b["pre"] / b["post"], 3
                )
                print(
                    f"  dcn bytes: {b['pre']:.0f} pre / {b['post']:.0f} "
                    f"post = {results['collective_dcn_bytes_ratio']}x",
                    flush=True,
                )
        # Members destroy first (each tears down the hierarchical subgroup
        # coordinators it owns — killing them outright would leak those
        # actors into the rest of the timed run), then the driver reaps
        # any parent state left behind.
        try:
            ray_tpu.get([m.destroy.remote() for m in members], timeout=60)
        except Exception:
            pass
        from ray_tpu.util import collective as _col

        _col.destroy_collective_group(row)
        for m in members:
            ray_tpu.kill(m)

    # Transport counters: the strace-free syscall-reduction view
    # (PERF.md round-6 A/B rides these).
    from ray_tpu.core import api as _api

    t = _api.transport_stats()
    if t:
        results["transport_frames_sent"] = t["frames_sent"]
        results["transport_writes"] = t["writes"]
        results["transport_frames_per_write"] = round(
            t["frames_per_write"], 3
        )
        results["transport_drains_skipped"] = t["drains_skipped"]
        print(
            f"transport: {t['frames_sent']} frames / {t['writes']} writes "
            f"= {t['frames_per_write']:.2f} frames/write "
            f"(max {t['max_frames_per_write']}, drains awaited "
            f"{t['drains']}, skipped {t['drains_skipped']})",
            flush=True,
        )

    print(json.dumps(results), flush=True)
    ray_tpu.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
