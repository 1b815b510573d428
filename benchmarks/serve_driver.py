"""One run of a serve cell (traffic kinds ``open-loop`` and ``closed-loop``).

The path is the one a user takes: ``serve.run(build_openai_app(...))`` with
one replica leasing ``num_tpus=1``, requests over HTTP with SSE streaming
through the proxy, the client in this process (which never touches JAX, so
the replica owns the chip). Token times are taken here, at the client.
"""

from __future__ import annotations

import http.client
import json
import os
import subprocess
import sys
import threading
import time

from benchmarks import harness, stats, traffic_gen
from benchmarks.harness import log, note

DEPLOYMENT = "llm"


def stream_completion(port: int, req, deadline, stop) -> dict:
    """POST one streaming completion; the wall time of every token event.
    ``stop`` (closed loop) ends the read at the window's cut."""
    rec = {
        "index": req.index, "prompt_tokens": req.prompt_tokens,
        "max_tokens": req.max_tokens, "tokens": [], "ok": False,
        "usage": None, "cut": False, "error": None,
    }
    body = json.dumps({
        "prompt": traffic_gen.prompt_text(req),
        "max_tokens": req.max_tokens, "stream": True,
    }).encode()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=deadline)
    try:
        rec["sent"] = time.time()
        conn.request(
            "POST", f"/{DEPLOYMENT}/v1/completions", body=body,
            headers={"Content-Type": "application/json", "Accept": "text/event-stream"},
        )
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"HTTP {resp.status}"
            return rec
        for raw in resp:
            now = time.time()
            if stop is not None and stop.is_set():
                rec["cut"] = True
                return rec
            if not raw.startswith(b"data: "):
                continue
            if raw.startswith(b"data: [DONE]"):
                # An answer may end early at the tokenizer's EOS (a random
                # model emits it about once in vocab-size tokens); the stop
                # token itself is not streamed. Anything else is a failure.
                rec["ok"] = rec["usage"] == len(rec["tokens"]) <= req.max_tokens
                if not rec["ok"]:
                    rec["error"] = f"{len(rec['tokens'])} token events, usage {rec['usage']}"
                return rec
            if b'"error"' in raw:
                rec["error"] = raw.decode(errors="replace").strip()[:300]
                return rec
            if b'"usage"' in raw:
                rec["usage"] = json.loads(raw[6:])["usage"]["completion_tokens"]
            else:
                rec["tokens"].append(now)
        rec["error"] = "stream ended without [DONE]"
        return rec
    except Exception as e:  # noqa: BLE001 -- a failed request is an outcome
        rec["error"] = f"{type(e).__name__}: {e}"
        return rec
    finally:
        rec["end"] = time.time()
        conn.close()


def run_open_loop(port: int, mix: dict, seed: int, seconds: float) -> tuple:
    """Every request at its due time, whatever the server is doing; all of
    them waited for (the tail is the tail of all requests)."""
    sched = traffic_gen.open_loop(mix, seed, seconds)
    records: list = [None] * len(sched)
    threads = []
    t_base = time.time() + 0.05

    def fire(req):
        rec = stream_completion(port, req, 300, None)
        rec["due"] = t_base + req.due_s
        records[req.index] = rec

    for req in sched:
        delay = t_base + req.due_s - time.time()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=fire, args=(req,), daemon=True)
        th.start()
        threads.append(th)
    t_offered = time.time()
    for th in threads:
        th.join(timeout=330)
    t_end = time.time()
    note(f"open loop: {len(sched)} requests offered over {t_offered - t_base:.2f}s, "
         f"drained {t_end - t_offered:.2f}s later")
    return [r for r in records if r is not None], t_base, t_end


def run_closed_loop(port: int, mix: dict, seed: int, seconds: float) -> tuple:
    """``clients`` callers, each sending its next request when its last one
    completes; the window is cut at ``seconds``."""
    deck = traffic_gen.closed_loop_deck(mix, seed, seconds)
    lock, stop = threading.Lock(), threading.Event()
    records: list = []
    t_base = time.time() + 0.05
    t_cut = t_base + seconds

    def client():
        while not stop.is_set():
            with lock:
                req = next(deck)
            rec = stream_completion(port, req, 300, stop)
            rec["due"] = rec["sent"]
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=client, daemon=True) for _ in range(mix["clients"])]
    time.sleep(max(0.0, t_base - time.time()))
    for th in threads:
        th.start()
    time.sleep(max(0.0, t_cut - time.time()))
    stop.set()
    for th in threads:
        th.join(timeout=30)
    with lock:
        out = sorted(records, key=lambda r: r["index"])
    done = sum(1 for r in out if r["ok"])
    note(f"closed loop: {len(out)} requests sent, {done} complete, "
         f"{sum(1 for r in out if r['cut'])} in flight at the cut")
    return out, t_base, t_cut


def replica_call(ray_tpu, method: str, *args, timeout=120):
    from ray_tpu.core import serialization
    from ray_tpu.serve import api as serve

    (rid,) = serve.status()[DEPLOYMENT]["replica_ids"]
    payload = serialization.dumps((args, {}))[0]
    return ray_tpu.get(
        ray_tpu.ActorHandle(rid, "Replica").handle.remote(method, payload),
        timeout=timeout,
    )


def output_check(cell: dict, seed: int, out_dir: str, rehearsal: int) -> dict:
    """One seeded sample of the program's prefill and decode against the
    plain reference, in a process of its own once the replica has let go of
    the chip. ``check.py`` is the same code over many seeds."""
    cmd = [sys.executable, os.path.join(harness.HERE, "check.py"),
           "--config", cell["config"], "--traffic", cell["traffic"],
           "--seeds", str(seed), "--who", "program"]
    if rehearsal:
        cmd += ["--cpu-rehearsal"]
    with open(os.path.join(out_dir, "check.err"), "w") as err:
        out = subprocess.run(cmd, stdout=subprocess.PIPE, stderr=err, text=True, timeout=900)
    if out.returncode != 0:
        raise SystemExit(f"output check failed to run (see {out_dir}/check.err)")
    return json.loads(out.stdout.strip().splitlines()[-1])


def deploy(cell: dict, config: dict, mix: dict, seed: int, rehearsal: int):
    """Runtime up, one replica deployed through ``build_openai_app``, every
    prefill bucket of the mix and the decode program warm. Returns
    ``(ray_tpu, port)``; ``teardown`` undoes it."""
    import ray_tpu.llm.serve_llm as serve_llm
    from ray_tpu.serve import api as serve

    from benchmarks import model_build
    from benchmarks.serve_replica import BenchLLMServer

    ray_tpu = harness.start_runtime(cell["chips"], rehearsal)
    try:
        log("serve.run(build_openai_app(...)): one replica leasing num_tpus=1")
        # build_openai_app deploys whatever serve_llm.LLMServer names: the
        # subclass changes nothing of the serving path (serve_replica.py).
        serve_llm.LLMServer = BenchLLMServer
        serve.run(
            serve_llm.build_openai_app(model_build.llm_config(config, mix, seed)),
            port=0, wait_timeout_s=1150,
        )
        port = serve.proxy_port()
        for req in traffic_gen.warm_requests(mix):
            t = time.time()
            rec = stream_completion(port, req, 1150, None)
            if not rec["ok"]:
                raise SystemExit(f"warm-up request failed: {rec['error']}")
            log(f"warm: prompt {req.prompt_tokens} -> {req.max_tokens} tokens in {time.time() - t:.2f}s")
    except BaseException:
        teardown(ray_tpu)
        raise
    return ray_tpu, port


def teardown(ray_tpu) -> None:
    """Stop serving and the runtime, and wait until every process they
    started has ended: only then is the chip free for the next process."""
    from ray_tpu.serve import api as serve

    started = harness.run_processes()
    try:
        serve.shutdown()
    finally:
        try:
            ray_tpu.shutdown()
        finally:
            harness.wait_until_ended(started, more=harness.run_processes)


def run(cell: dict, args, out_dir: str) -> int:
    config, mix = harness.cell_files(cell, args.cpu_rehearsal)
    trace_dir = os.path.join(out_dir, "trace")
    ray_tpu, port = deploy(cell, config, mix, args.seed, args.cpu_rehearsal)
    try:
        setup_s = time.time() - harness.T_PROCESS_START

        tracer = None
        anchor = {}
        if args.trace:
            tw = mix["trace_window"]
            start = min(tw["start_s"], max(0.0, args.seconds - tw["seconds"] - 1.0))

            def traced():
                time.sleep(start)
                anchor["wall_ns"] = replica_call(ray_tpu, "bench_trace_start", trace_dir)
                time.sleep(min(tw["seconds"], args.seconds))
                replica_call(ray_tpu, "bench_trace_stop", timeout=300)

            tracer = threading.Thread(target=traced, daemon=True)
            tracer.start()
        loop = run_open_loop if mix["kind"] == "open-loop" else run_closed_loop
        requests, t0, t1 = loop(port, mix, args.seed, args.seconds)
        if tracer is not None:
            tracer.join(timeout=330)
        replica = replica_call(ray_tpu, "bench_report")
        log(f"window done: {len(requests)} requests; replica {json.dumps(replica['stats'])}")
    finally:
        teardown(ray_tpu)
    harness.check_device(replica["device"], cell["chips"], args.cpu_rehearsal)

    check = output_check(cell, args.seed, out_dir, args.cpu_rehearsal)
    limits = harness.load_json(harness.HERE, "limits", cell["config"] + ".json")
    correct = all(
        harness.compared(name, check["rows"][0][name], limit)
        for name, limit in limits["limits"].items()
    )
    failed = [r for r in requests if not r["ok"] and not r["cut"]]
    correct &= harness.compared("requests_failed_or_short", len(failed), 0)
    for r in failed[:5]:
        note(f"failed request {r['index']}: {r['error']}")

    reduced = None
    if args.trace:
        reduced = harness.reduce_trace(
            trace_dir, anchor.get("wall_ns"), replica["spans"], out_dir, args.cpu_rehearsal
        )

    ok = [r for r in requests if r["tokens"]]
    note(f"answers that ended before max_tokens (EOS): "
         f"{sum(1 for r in requests if r['ok'] and r['usage'] < r['max_tokens'])} of {len(requests)}")
    late = stats.lateness_ms(requests)
    note(f"generator lateness ms: p50 {stats.percentile(late, 50):.3f} max {max(late):.3f} "
         f"over {len(requests)} requests")
    note(f"client ttft ms: p50 {stats.percentile(stats.ttft_ms(ok), 50):.3f} "
         f"p90 {stats.percentile(stats.ttft_ms(ok), 90):.3f}; "
         f"itl samples {len(stats.itl_ms(ok))}; tokens in window {stats.tokens_in_window(ok, t0, t1)}")
    ev = replica["compile_events"]
    note(f"compile cache: replica {sum(k == 'cache_hit' for _, k in ev)} hits "
         f"{sum(k == 'cache_miss' for _, k in ev)} misses; check {json.dumps(check['compile_cache'])}")
    note(f"allocator peak: replica {replica['device']['memory_peak_bytes']} B of "
         f"{replica['device']['bytes_limit']} B, check {check['device']['memory_peak_bytes']} B; "
         f"weights {replica['weight_bytes']} B, pool {replica['pool_bytes']} B")
    records = {
        "cell": cell, "config": config, "traffic": mix, "seconds": args.seconds,
        "setup_s": setup_s, "window": [t0, t1], "requests": requests,
        "engine_stats": replica["stats"], "spans": replica["spans"],
        "compile_events": ev, "trace": reduced,
        "peaks": None if args.cpu_rehearsal else harness.peaks_for(replica["device"]["kind"]),
    }
    harness.save(out_dir, "requests.json", {"window": [t0, t1], "records": requests})
    harness.save(out_dir, "replica.json", {k: v for k, v in replica.items() if k != "spans"})
    harness.save(out_dir, "spans.json", replica["spans"])
    metrics = harness.read_metrics(cell["name"], bool(args.trace), records)
    device, breakdown = harness.device_line(replica["device"], reduced)
    harness.result_line(correct, len(requests), len(failed), metrics, device, breakdown)
    return 0
