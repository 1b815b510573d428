"""Headline benchmark: GPT-2-125M training throughput per TPU chip.

Prints ONE JSON line:
  {"metric": "gpt2_125m_train_tokens_per_sec_per_chip", "value": N,
   "unit": "tokens/s/chip", "vs_baseline": N / BASELINE}

Baseline: the north star (BASELINE.json) is matching 8xA100 TorchTrainer+NCCL
tokens/sec/chip for GPT-2-125M. No measured reference number is checked in
(`published: {}`), so we use 100_000 tokens/s/chip — an estimate for a single
A100 on GPT-2-125M bf16 at ~25-30% MFU (312 TFLOPs peak, ~6·N FLOPs/token).
vs_baseline > 1.0 means beating that estimate per chip.

The parent process never imports jax: a parent that has touched JAX holds
the chip, and the measurement runs in a subprocess. With no TPU the
measurement fails and this script exits non-zero naming the platform jax
found; no path prints a result without a chip.

Runs on however many chips are visible (the driver gives one); uses a dp mesh
over all local devices and reports per-chip throughput.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

BASELINE_TOKENS_PER_SEC_PER_CHIP = 100_000.0
METRIC = "gpt2_125m_train_tokens_per_sec_per_chip"
BENCH_TIMEOUT_S = float(os.environ.get("RAY_TPU_BENCH_TIMEOUT_S", "1500"))


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_bench() -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ray_tpu.models import gpt2
    from ray_tpu.parallel import (
        DEFAULT_RULES,
        MeshSpec,
        make_mesh,
        shardings_from_logical,
    )
    from ray_tpu.train.spmd import (
        compile_train_step,
        default_optimizer,
        make_train_state,
        make_train_step,
    )

    import dataclasses

    smoke = bool(os.environ.get("RAY_TPU_BENCH_SMOKE"))
    devices = jax.devices()
    n_dev = len(devices)
    _log(f"bench devices: {n_dev} x {devices[0].device_kind}")
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; jax found platform "
            f"{devices[0].platform!r} ({devices[0].device_kind})"
        )

    if smoke:
        base = gpt2.GPT2Config.tiny()
        candidates = [(8, base)]
        warmup, iters = 1, 2
    else:
        base = gpt2.GPT2Config.gpt2_125m()
        # (per-chip batch, config) in preference order. Round-4 sweep on
        # v5e: B=8 with the chunked-loss scan DISABLED (loss_chunk=0) wins
        # — the full [8, S, vocab] f32 logits fit HBM at B=8 and skipping
        # the chunk scan's extra lm-head remat matmul is worth ~13%
        # (78.9 ms vs 90.2 ms/step = 103.8k vs 90.8k tok/s/chip). Larger
        # batches must keep chunking (logits would be 3-10 GB) and
        # measured slower per token; they remain as OOM backoffs.
        candidates = [
            (8, dataclasses.replace(base, loss_chunk=0)),
            (12, dataclasses.replace(base, loss_chunk=0)),
            (24, base),
            (8, base),
        ]
        warmup, iters = 3, 10

    opt = default_optimizer(total_steps=1000)

    def measure_one(per_chip_batch, cfg):
        mesh = make_mesh(MeshSpec(dp=n_dev), devices)
        shardings = shardings_from_logical(
            gpt2.param_logical_specs(cfg), DEFAULT_RULES, mesh
        )
        seq = cfg.max_seq
        B = per_chip_batch * n_dev
        state = make_train_state(
            lambda k: gpt2.init_params(k, cfg),
            opt,
            jax.random.key(0),
            param_shardings=shardings,
        )
        step = make_train_step(
            lambda p, b: gpt2.loss_fn(p, b, cfg, mesh=mesh),
            opt,
            mesh=mesh,
            batch_spec=P(("dp", "fsdp")),
            param_shardings=shardings,
        )
        tokens = jax.random.randint(
            jax.random.key(1), (B, seq), 0, cfg.vocab_size
        )
        batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
        # AOT: trace + XLA-compile during setup so neither ever lands in
        # the measured window (warmup still absorbs autotuning/transfer),
        # and the executable's own cost model gives a device-verified
        # flops/step to cross-check tok/s against.
        t0 = time.perf_counter()
        compiled, step_flops = compile_train_step(step, state, batch)
        _log(
            f"AOT compile (B={B}, chunk={cfg.loss_chunk}) in "
            f"{time.perf_counter() - t0:.1f}s"
            + (f", {step_flops:.3e} flops/step" if step_flops else "")
        )
        t0 = time.perf_counter()
        for _ in range(warmup):
            state, metrics = compiled(state, batch)
        # float() forces a device->host transfer, which waits for the step.
        loss_val = float(metrics["loss"])
        _log(
            f"warmup done (B={B}, chunk={cfg.loss_chunk}) in "
            f"{time.perf_counter() - t0:.1f}s, loss={loss_val:.4f}"
        )
        # The timed loop is host-free by construction: N async dispatches,
        # one sync at the end — the host never sits between steps.
        t0 = time.perf_counter()
        for _ in range(iters):
            state, metrics = compiled(state, batch)
        float(metrics["loss"])
        dt = time.perf_counter() - t0
        per_chip = B * seq * iters / dt / n_dev
        _log(
            f"B={B} seq={seq} chunk={cfg.loss_chunk}: "
            f"{per_chip:,.0f} tok/s/chip ({dt / iters * 1e3:.1f} ms/step)"
        )
        if step_flops:
            # Device-verified cross-check: achieved FLOP/s from the
            # executable's own cost model vs the token-count arithmetic.
            tflops = step_flops * iters / dt / 1e12 / n_dev
            _log(
                f"  cost-model cross-check: {step_flops / (B * seq):,.0f} "
                f"flops/token -> {tflops:.2f} TFLOP/s/chip at the measured "
                f"step time"
            )
        return per_chip, step_flops

    # Measure the first TWO viable candidates and report the better one
    # (the preference order is from the sweep, but toolchains drift; one
    # extra ~60 s measurement buys a verified choice). OOM backs off
    # to the next candidate; other errors surface immediately.
    best = 0.0
    best_flops = None
    measured = 0
    last_err = None
    for per_chip_batch, cfg in candidates:
        if measured >= 2:
            break
        try:
            per_chip, step_flops = measure_one(per_chip_batch, cfg)
            if per_chip > best:
                best, best_flops = per_chip, step_flops
            measured += 1
        except Exception as e:
            msg = f"{type(e).__name__}: {e}"
            oom = any(
                s in msg
                for s in ("RESOURCE_EXHAUSTED", "Out of memory", "OOM", "hbm")
            )
            if not oom:
                if best > 0.0:
                    # Report what we have rather than forfeit the round,
                    # but LOUDLY: a broken candidate is a real bug.
                    _log(
                        f"candidate B={per_chip_batch} "
                        f"chunk={cfg.loss_chunk} failed NON-OOM "
                        f"(reporting earlier result): {msg[:500]}"
                    )
                    break
                raise
            last_err = e
            _log(f"candidate B={per_chip_batch} OOM; backing off")
    if best == 0.0:
        raise RuntimeError(f"all candidates failed; last error: {last_err}")
    record = {
        "metric": METRIC,
        "value": round(best, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(best / BASELINE_TOKENS_PER_SEC_PER_CHIP, 4),
    }
    if best_flops:
        record["step_flops"] = best_flops
    return record


def _data_plane_rows() -> dict:
    """Large-object data-plane rows (put_large / get_large /
    actor_array_args, MB/s) via ``tools/ray_perf.py --data-plane-only``.
    CPU-only and best-effort: any failure returns {} so the headline
    one-JSON-line contract stands."""
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    try:
        r = subprocess.run(
            [
                sys.executable,
                os.path.join(repo, "tools", "ray_perf.py"),
                "--quick",
                "--data-plane-only",
            ],
            timeout=420,
            capture_output=True,
            text=True,
            env=env,
            cwd=repo,
        )
        if r.returncode != 0:
            _log(f"data-plane rows failed rc={r.returncode}; skipping")
            return {}
        for line in reversed(r.stdout.strip().splitlines()):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    except Exception as e:  # noqa: BLE001 — never fail the headline bench
        _log(f"data-plane rows skipped: {type(e).__name__}: {e}")
    return {}


def _one_arm(label: str, flags: tuple, timeout_s: int) -> dict | None:
    """One ``tools/ray_perf.py --quick`` run; returns its JSON row dict,
    or None on any failure (CPU-only, best-effort — callers drop the
    whole record so a one-armed A/B never lands)."""
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    try:
        r = subprocess.run(
            [
                sys.executable,
                os.path.join(repo, "tools", "ray_perf.py"),
                "--quick",
                *flags,
            ],
            timeout=timeout_s,
            capture_output=True,
            text=True,
            env=env,
            cwd=repo,
        )
        if r.returncode != 0:
            _log(f"{label} failed rc={r.returncode}; skipping")
            return None
        for line in reversed(r.stdout.strip().splitlines()):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
        _log(f"{label} produced no JSON; skipping")
    except Exception as e:  # noqa: BLE001 — never fail the headline
        _log(f"{label} skipped: {type(e).__name__}: {e}")
    return None


def _ab_rows(
    label: str, base_flags: tuple, off_flags: tuple, timeout_s: int
) -> dict:
    """Shared ON/OFF A/B runner: the ON arm runs HEAD defaults, the OFF
    arm adds the kill-switch flags. All-or-nothing (a one-armed record
    would break round-over-round diffs)."""
    out: dict = {}
    for arm, flags in (("on", ()), ("off", off_flags)):
        row = _one_arm(f"{label} arm {arm}", base_flags + flags, timeout_s)
        if row is None:
            return {}
        out[arm] = row
    return out


def _serve_llm_rows() -> dict:
    """LLM-serving A/B record (round-12): aggregate tok/s + p99 TTFT with
    prefix-affinity routing ON vs OFF (``--no-prefix-routing``)."""
    out = _ab_rows(
        "serve_llm", ("--serve-llm-only",), ("--no-prefix-routing",), 600
    )
    if "on" in out and "off" in out:
        on_t = out["on"].get("serve_llm_shared_prefix", 0)
        off_t = out["off"].get("serve_llm_shared_prefix", 0)
        if off_t:
            out["shared_prefix_tok_s_ratio"] = round(on_t / off_t, 3)
    return out


def _serve_disagg_rows(serve_llm: dict) -> dict:
    """Disaggregated-serving + speculative-decoding A/B record (round
    16): the decode-stall probe (cold long prompt joins the decode
    engine as a KV handoff vs local prefill) and the spec-decode rows
    (tok/s, per-token p99, accept rate). The ON arm is REUSED from the
    serve_llm record (byte-identical ray_perf command — running it twice
    would burn ~10 min of bench budget for the same numbers); only the
    OFF arm (``--no-disagg --no-spec-decode``) runs here."""
    on = (serve_llm or {}).get("on")
    if not on or "serve_llm_disagg_stall_ms" not in on:
        return {}
    off = _one_arm(
        "serve_disagg arm off",
        ("--serve-llm-only", "--no-disagg", "--no-spec-decode"),
        700,
    )
    if off is None:
        return {}
    out = {"on": on, "off": off}
    on_s = on.get("serve_llm_disagg_stall_ms", 0)
    off_s = off.get("serve_llm_disagg_stall_ms", 0)
    if on_s:
        # >1 = the handoff bounded the stall local prefill paid.
        out["disagg_stall_off_on_ratio"] = round(off_s / on_s, 3)
    on_t = on.get("serve_llm_spec_decode_tok_s", 0)
    off_t = off.get("serve_llm_spec_decode_tok_s", 0)
    if off_t:
        out["spec_decode_tok_s_ratio"] = round(on_t / off_t, 3)
    return out


def _serve_overload_rows() -> dict:
    """Overload-protection A/B record (round-15): shed rate +
    admitted-interactive p99 under a SEEDED flash crowd
    (tools/traffic_gen.py) with the admission plane ON vs OFF
    (``--no-admission``). Both arms replay the same seed-7 arrival
    schedule."""
    out = _ab_rows(
        "serve_overload", ("--serve-overload",), ("--no-admission",), 420
    )
    if "on" in out and "off" in out:
        on_p99 = out["on"].get("serve_overload_admitted_p99_ttft_ms", 0)
        off_p99 = out["off"].get("serve_overload_admitted_p99_ttft_ms", 0)
        if on_p99:
            # >1 = the plane bounded the interactive tail the OFF arm paid.
            out["admitted_p99_off_on_ratio"] = round(off_p99 / on_p99, 3)
    return out


def _obs_overhead_rows() -> dict:
    """Observability-plane overhead A/B (round-20): the serve p99 probe
    (seeded flash crowd, admission ON) with the flight recorder ON (HEAD
    default: every hop records a ring event) vs OFF
    (``--no-flightrec``, the RAY_TPU_FLIGHTREC=0 kill switch). The
    acceptance bar is ON p99 within ~3% of OFF."""
    out = _ab_rows(
        "obs_overhead", ("--serve-overload",), ("--no-flightrec",), 420
    )
    if "on" in out and "off" in out:
        on_p99 = out["on"].get("serve_overload_admitted_p99_ttft_ms", 0)
        off_p99 = out["off"].get("serve_overload_admitted_p99_ttft_ms", 0)
        if off_p99:
            # The recorder's tax on the interactive tail; <=3% is green.
            out["p99_overhead_pct"] = round(
                (on_p99 / off_p99 - 1.0) * 100.0, 2
            )
    return out


def _train_overlap_rows() -> dict:
    """Host-free train-step A/B (round-13): steps/s + host-blocked ms per
    step with async dispatch + device prefetch ON vs the kill-switch arm
    (``--no-async-dispatch``); pure-jax single-process loop."""
    out = _ab_rows(
        "train_overlap", ("--train-only",), ("--no-async-dispatch",), 420
    )
    if "on" in out and "off" in out:
        on_b = out["on"].get("train_step_host_blocked_ms", 0)
        off_b = out["off"].get("train_step_host_blocked_ms", 0)
        if on_b:
            out["host_blocked_off_on_ratio"] = round(off_b / on_b, 3)
        on_s = out["on"].get("train_step_overlap", 0)
        off_s = out["off"].get("train_step_overlap", 0)
        if off_s:
            out["steps_per_s_ratio"] = round(on_s / off_s, 3)
    return out


def _train_elastic_rows() -> dict:
    """Elastic-recovery A/B (round-21): preempt-to-first-step latency on
    a 2-node gang that loses a node to a graceful drain notice mid-run,
    with live re-formation ON (pause -> peer reshard -> resume in the
    same generation) vs the kill-switch arm (``--no-elastic``: tear down
    and rebuild from the latest checkpoint). Both arms stamp the same
    drain-seen -> first-post-recovery-report interval."""
    out = _ab_rows(
        "train_elastic",
        ("--train-only", "--elastic-probe"),
        ("--no-elastic",),
        420,
    )
    if "on" in out and "off" in out:
        on_ms = out["on"].get("train_elastic_recovery_ms") or 0
        off_ms = out["off"].get("train_elastic_recovery_ms") or 0
        if on_ms:
            # >1 = re-forming live beat the checkpoint round trip.
            out["recovery_off_on_ratio"] = round(off_ms / on_ms, 3)
    return out


def _podracer_rows() -> dict:
    """Podracer decoupled-RL A/B (round-17): env_steps/s + learner
    updates/s + weight-lag p99 on the emulated-cost CartPole with the
    actor/inference/learner planes ON vs the kill-switch arm
    (``--no-podracer``: the single-loop sample→update DQN iteration)."""
    out = _ab_rows("podracer", ("--rl-only",), ("--no-podracer",), 900)
    if "on" in out and "off" in out:
        on_s = out["on"].get("rl_env_steps_per_s", 0)
        off_s = out["off"].get("rl_env_steps_per_s", 0)
        if off_s:
            # >1 = decoupling actually bought acting throughput.
            out["env_steps_per_s_ratio"] = round(on_s / off_s, 3)
    return out


def _data_governor_rows() -> dict:
    """Memory-governed data-plane A/B (round-18): out-of-core pipeline
    rows/s + peak store occupancy + spill count with the governor ON vs
    the kill-switch arm (``--no-data-governor``). The workload caps the
    object store 4x below the dataset, so the OFF arm spills where the
    ON arm stays under the high watermark."""
    out = _ab_rows(
        "data_governor", ("--data-only",), ("--no-data-governor",), 420
    )
    if "on" in out and "off" in out:
        on_r = out["on"].get("data_pipeline_rows_per_s", 0)
        off_r = out["off"].get("data_pipeline_rows_per_s", 0)
        if off_r:
            # >1 = bounded-memory streaming beat spill-and-restore.
            out["rows_per_s_ratio"] = round(on_r / off_r, 3)
    return out


def _fleet_scale_rows() -> dict:
    """Fleet-scale control-plane A/B (round-19): placement p50/p99 at
    100/500/1,000 emulated nodes with the feasibility-indexed scheduler
    ON vs the full-scan kill-switch arm (``--no-sched-index``). Both arms
    replay the same seeded lease schedule through the in-process fleet
    emulator — no cluster runtime."""
    out = _ab_rows(
        "fleet_scale", ("--fleet-only",), ("--no-sched-index",), 420
    )
    if "on" in out and "off" in out:
        on_p99 = out["on"].get("fleet_place_p99_ms_1000", 0)
        off_p99 = out["off"].get("fleet_place_p99_ms_1000", 0)
        if on_p99:
            # >1 = the bounded-sample index beat the scan; the round-19
            # acceptance bar is >=2.0 on this row.
            out["place_p99_1000_off_on_ratio"] = round(off_p99 / on_p99, 3)
    return out


def _raylint_rows() -> dict:
    """Static-analysis debt counts via ``tools/raylint.py --json`` (total /
    suppressed / unsuppressed + per-rule) so lint debt is tracked per round
    like perf. Best-effort: any failure returns {} so the headline
    one-JSON-line contract stands."""
    repo = os.path.dirname(os.path.abspath(__file__))
    try:
        r = subprocess.run(
            [
                sys.executable,
                os.path.join(repo, "tools", "raylint.py"),
                "--json",
            ],
            timeout=120,
            capture_output=True,
            text=True,
            cwd=repo,
        )
        # rc 1 = unsuppressed findings: still a valid, very interesting row.
        payload = json.loads(r.stdout.strip().splitlines()[-1])
        return {
            "total": payload["total"],
            "suppressed": payload["suppressed"],
            "unsuppressed": payload["unsuppressed"],
            "advisory": payload.get("advisory", 0),
            "by_rule": payload["by_rule"],
            # Lock-graph summary (RL105): nodes/edges of the cross-file
            # lock-acquisition graph; cycles must stay 0 — tracked per
            # round like the finding counts.
            "lock_graph": payload.get(
                "lock_graph", {"nodes": 0, "edges": 0, "cycles": 0}
            ),
        }
    except Exception as e:  # noqa: BLE001 — never fail the headline bench
        _log(f"raylint rows skipped: {type(e).__name__}: {e}")
    return {}


def _emit(
    record: dict,
    data_plane: dict,
    serve_llm: dict | None = None,
    raylint: dict | None = None,
    train_overlap: dict | None = None,
    train_elastic: dict | None = None,
    serve_overload: dict | None = None,
    serve_disagg: dict | None = None,
    podracer: dict | None = None,
    data_governor: dict | None = None,
    fleet_scale: dict | None = None,
    obs_overhead: dict | None = None,
) -> None:
    if data_plane:
        record = {**record, "data_plane": data_plane}
    if data_governor:
        # Memory-governed data-plane A/B (occupancy bound + spill count,
        # governor ON vs kill switch) rides every record from round 18 on.
        record = {**record, "data_governor": data_governor}
    if fleet_scale:
        # Fleet-scale scheduler A/B (feasibility index ON vs full-scan
        # kill switch at 1,000 emulated nodes) rides every record from
        # round 19 on.
        record = {**record, "fleet_scale": fleet_scale}
    if serve_llm:
        # Serving A/B rides every record too: the BENCH trajectory tracks
        # the serving number (tok/s + p99 TTFT, routing ON vs OFF) from
        # round 12 on.
        record = {**record, "serve_llm": serve_llm}
    if serve_disagg:
        # Disagg + spec-decode A/B (stall probe, tok/s, accept rate)
        # rides every record from round 16 on.
        record = {**record, "serve_disagg": serve_disagg}
    if serve_overload:
        # Overload-protection A/B (admission ON vs OFF under the seeded
        # flash crowd) rides every record from round 15 on.
        record = {**record, "serve_overload": serve_overload}
    if obs_overhead:
        # Flight-recorder overhead A/B (recorder ON vs --no-flightrec on
        # the serve p99 probe) rides every record from round 20 on.
        record = {**record, "obs_overhead": obs_overhead}
    if train_overlap:
        # Train-overlap A/B (async dispatch + prefetch ON vs kill switch)
        # rides every record like data_plane/serve_llm from round 13 on.
        record = {**record, "train_overlap": train_overlap}
    if train_elastic:
        # Elastic-recovery A/B (live re-formation ON vs --no-elastic
        # checkpoint rebuild) rides every record from round 21 on.
        record = {**record, "train_elastic": train_elastic}
    if podracer:
        # Podracer decoupled-RL A/B (planes ON vs --no-podracer) rides
        # every record from round 17 on.
        record = {**record, "podracer": podracer}
    if raylint:
        # Lint-debt counts ride every record (tracked like perf: the
        # suppressed count is the justified-debt baseline; unsuppressed
        # must stay 0 — tests/test_raylint.py enforces it in tier-1).
        record = {**record, "raylint": raylint}
    print(json.dumps(record), flush=True)


def main() -> None:
    if "--run" in sys.argv:
        # Measurement subprocess: this is the only process that imports jax.
        print(json.dumps(run_bench()), flush=True)
        return

    from ray_tpu.util.compile_cache import ensure_compile_cache

    ensure_compile_cache()  # inherited by the measurement subprocess
    # The chip cell first: without a TPU nothing else is worth printing.
    # stdout captured for the one-JSON-line contract; stderr inherited so
    # progress logs stream live and survive a timeout kill.
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--run"],
            timeout=BENCH_TIMEOUT_S,
            stdout=subprocess.PIPE,
            text=True,
        )
    except subprocess.TimeoutExpired:
        _log(f"bench subprocess exceeded {BENCH_TIMEOUT_S}s")
        sys.exit(1)
    if r.returncode != 0:
        _log(f"bench subprocess failed rc={r.returncode}")
        sys.exit(1)
    record = None
    for line in reversed(r.stdout.strip().splitlines()):
        if line.strip().startswith("{"):
            record = json.loads(line)
            break
    if record is None:
        _log("bench subprocess printed no record")
        sys.exit(1)

    # Host-plane rows: CPU-only guards that ride the same record.
    data_plane = _data_plane_rows()
    serve_llm = _serve_llm_rows()
    serve_disagg = _serve_disagg_rows(serve_llm)
    serve_overload = _serve_overload_rows()
    obs_overhead = _obs_overhead_rows()
    train_overlap = _train_overlap_rows()
    train_elastic = _train_elastic_rows()
    podracer = _podracer_rows()
    data_governor = _data_governor_rows()
    fleet_scale = _fleet_scale_rows()
    raylint = _raylint_rows()
    _emit(
        record, data_plane, serve_llm, raylint,
        train_overlap, train_elastic, serve_overload, serve_disagg,
        podracer, data_governor, fleet_scale, obs_overhead,
    )


if __name__ == "__main__":
    main()
