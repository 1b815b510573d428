"""Quickest proof that the system still starts on the chip.

    python chip_smoke.py                      # on a host with TPU chips
    python chip_smoke.py --cpu-rehearsal [N]  # tiny sizes, N fake chips, CPU

Drives the two programs that run on the TPU through the entry points a user
calls, each inside worker processes that hold a ``TPU`` lease:

- train: ``JaxTrainer(...).fit()`` with one worker reserving every chip the
  node advertises; full-width GPT-2-125M (12 layers, 768 wide, 12 heads,
  vocabulary 50,304, S=1024, bf16 activations, ``attn_impl="auto"``), 8
  sequences per chip, ``loss_chunk=0``, ``make_train_step`` with donation on,
  a ``dp`` mesh over the local chips (and once more on ``fsdp=2, tp=2`` when
  there are four), a few steps, ``train.report`` each step;
- serve: ``serve.run(build_openai_app(LLMConfig(placement={"num_tpus": 1})))``
  with the default model (GPT-2-125M, paged cache), one replica per chip, then
  completions through the real HTTP proxy: prompts in two prefill buckets,
  one streamed over SSE.

This process never builds a JAX backend (a parent that has touched JAX holds
the chip); every fact it checks was read inside the process that ran the
step. Progress goes to stderr. Stdout gets two lines, only when every phase
passed: what the phases reported, then one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failure, another platform than the TPU, or fewer chips than the node
advertises exits non-zero without them. The CPU rehearsal exists to debug the
script itself and labels its output ``cpu``; it is never entered unless named
on the command line.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time
import urllib.request

import ray_tpu
from ray_tpu.util.compile_cache import ensure_compile_cache

TRAIN_STEPS = 5
SEQS_PER_CHIP = 8

# Worker processes import ray_tpu by name, from wherever this script lives.
os.environ["PYTHONPATH"] = os.pathsep.join(
    [os.path.dirname(os.path.abspath(__file__)),
     *filter(None, [os.environ.get("PYTHONPATH")])]
)


_T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- train phase --------------------------------------------------------------


def _train_loop(config: dict) -> None:
    """Runs inside the JaxTrainer worker (the process that owns the chips)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu import train
    from ray_tpu.models import gpt2
    from ray_tpu.ops import attention
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
    from ray_tpu.util.compile_cache import CacheCounter

    cache = CacheCounter()
    devices = jax.devices()
    if config["tiny"]:
        cfg = gpt2.GPT2Config.tiny(max_seq=128)
    else:
        cfg = gpt2.GPT2Config.gpt2_125m()
    cfg = dataclasses.replace(cfg, loss_chunk=0)
    mesh = make_mesh(MeshSpec(**config["mesh"]), devices)
    shardings = shardings_from_logical(
        gpt2.param_logical_specs(cfg), DEFAULT_RULES, mesh
    )
    opt = default_optimizer(total_steps=1000)
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
    B = config["seqs_per_chip"] * len(devices)
    tokens = jax.random.randint(
        jax.random.key(1), (B, cfg.max_seq), 0, cfg.vocab_size
    )
    batch = jax.device_put(
        {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)},
        NamedSharding(mesh, P(("dp", "fsdp"))),
    )
    t0 = time.monotonic()
    compiled, flops = compile_train_step(step, state, batch)
    compile_s = time.monotonic() - t0
    mosaic_calls = compiled.as_text().count("tpu_custom_call")

    losses = []
    t0 = time.monotonic()
    for i in range(config["steps"]):
        state, metrics = compiled(state, batch)
        losses.append(metrics["loss"])
        train.report({"step": i, "loss": metrics["loss"]})
    losses = [float(x) for x in losses]
    steps_s = time.monotonic() - t0

    # The flash kernel against the repo's own O(S^2) reference, on this
    # device, small enough to hold the scores.
    kernel_err = None
    if devices[0].platform == "tpu":
        q, k, v = (
            jax.random.normal(kk, (2, 4, 512, 64), jnp.bfloat16)
            for kk in jax.random.split(jax.random.key(2), 3)
        )
        flash = attention.causal_attention(q, k, v, impl="pallas")
        ref = attention.causal_attention(q, k, v, impl="reference")
        kernel_err = float(
            jnp.max(jnp.abs(flash.astype(jnp.float32) - ref.astype(jnp.float32)))
        )

    stats = devices[0].memory_stats() or {}
    train.report(
        {
            "evidence": {
                "pid": os.getpid(),
                "platform": devices[0].platform,
                "device_kind": devices[0].device_kind,
                "device_ids": [d.id for d in devices],
                "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
                "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
                "model": {
                    "n_layer": cfg.n_layer,
                    "d_model": cfg.d_model,
                    "n_head": cfg.n_head,
                    "vocab_size": cfg.vocab_size,
                    "seq": cfg.max_seq,
                    "dtype": np.dtype(cfg.dtype).name,
                    "attn_impl": cfg.attn_impl,
                },
                "batch": B,
                "losses": losses,
                "final_step": int(state["step"]),
                "mosaic_calls": mosaic_calls,
                "kernel_max_abs_err": kernel_err,
                "compile_s": round(compile_s, 1),
                "steps_s": round(steps_s, 2),
                "step_flops": flops,
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "compile_cache": cache.snapshot(),
            }
        }
    )


def train_phase(n_chips: int, mesh: dict, platform: str, storage: str) -> dict:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    name = "-".join(f"{k}{v}" for k, v in mesh.items())
    log(f"train[{name}]: JaxTrainer.fit(), one worker reserving TPU={n_chips}")
    trainer = JaxTrainer(
        _train_loop,
        train_loop_config={
            "mesh": mesh,
            "tiny": platform == "cpu",
            "steps": TRAIN_STEPS,
            "seqs_per_chip": 2 if platform == "cpu" else SEQS_PER_CHIP,
        },
        # Plain TPU count, no slice topology: only the chip count is certain
        # on a bare TPU VM, and nobody advertises a slice-head resource.
        scaling_config=ScalingConfig(
            num_workers=1, resources_per_worker={"TPU": n_chips}
        ),
        run_config=RunConfig(name=f"chip_smoke_{name}", storage_path=storage),
    )
    result = trainer.fit()
    reports = result.metrics_history
    check(bool(reports) and "evidence" in reports[-1],
          f"train[{name}]: the worker's last report carries no evidence")
    ev = reports[-1]["evidence"]
    log(f"train[{name}]: {json.dumps(ev)}")
    check(ev["platform"] == platform,
          f"train[{name}]: ran on platform {ev['platform']!r} "
          f"({ev['device_kind']}), not {platform!r}")
    check(len(ev["device_ids"]) == n_chips,
          f"train[{name}]: worker saw {len(ev['device_ids'])} devices, "
          f"the node advertises {n_chips} chips")
    check(len(ev["losses"]) == TRAIN_STEPS
          and all(math.isfinite(x) for x in ev["losses"]),
          f"train[{name}]: losses not finite: {ev['losses']}")
    check(ev["final_step"] == TRAIN_STEPS,
          f"train[{name}]: step counter at {ev['final_step']}")
    step_reports = [r for r in reports if "step" in r]
    check([r["step"] for r in step_reports] == list(range(TRAIN_STEPS))
          and [float(r["loss"]) for r in step_reports] == ev["losses"],
          f"train[{name}]: train.report history does not match the steps")
    # Random weights: the first loss sits at ln(vocab).
    check(abs(ev["losses"][0] - math.log(ev["model"]["vocab_size"])) < 0.5,
          f"train[{name}]: first loss {ev['losses'][0]} is not near "
          f"ln(vocab)")
    if platform == "tpu":
        check(ev["mosaic_calls"] >= 2,
              f"train[{name}]: compiled step holds {ev['mosaic_calls']} "
              f"Mosaic custom calls: the flash kernel did not run")
        check(ev["kernel_max_abs_err"] < 0.1,
              f"train[{name}]: flash kernel is {ev['kernel_max_abs_err']} "
              f"off the reference")
    return ev


# -- serve phase --------------------------------------------------------------


def _post(port: int, body: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/llm/v1/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=900) as resp:
        check(resp.status == 200, f"serve: HTTP {resp.status}")
        return json.loads(resp.read())


def _post_sse(port: int, body: dict) -> list:
    """POST with stream=true; the decoded ``data:`` events up to [DONE]."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/llm/v1/completions",
        data=json.dumps({**body, "stream": True}).encode(),
        headers={
            "Content-Type": "application/json",
            "Accept": "text/event-stream",
        },
    )
    events = []
    with urllib.request.urlopen(req, timeout=900) as resp:
        check(resp.status == 200, f"serve: SSE HTTP {resp.status}")
        check("text/event-stream" in resp.headers.get("Content-Type", ""),
              f"serve: SSE content type {resp.headers.get('Content-Type')}")
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            if line == "data: [DONE]":
                return events
            events.append(json.loads(line[len("data: "):]))
    raise SmokeFailure("serve: SSE stream ended without [DONE]")


def _replica_reports() -> list:
    from ray_tpu.core import serialization
    from ray_tpu.serve import api as serve

    payload = serialization.dumps(((), {}))[0]
    return ray_tpu.get(
        [
            ray_tpu.ActorHandle(rid, "Replica").handle.remote(
                "engine_report", payload
            )
            for rid in serve.status()["llm"]["replica_ids"]
        ],
        timeout=120,
    )


def serve_phase(n_replicas: int, platform: str) -> dict:
    from ray_tpu.llm.config import LLMConfig
    from ray_tpu.llm.serve_llm import build_openai_app
    from ray_tpu.serve import api as serve

    placement = {"num_tpus": 1, "num_cpus": 1}
    if platform == "cpu":
        from ray_tpu.models.gpt2 import GPT2Config

        config = LLMConfig(
            model_config=GPT2Config.tiny(max_seq=128),
            max_slots=4,
            max_seq=128,
            prefill_buckets=(32, 64, 128),
            placement=placement,
        )
    else:
        config = LLMConfig(placement=placement)  # GPT-2-125M, paged cache
    log(f"serve: {n_replicas} LLMServer replica(s), each leasing num_tpus=1")
    serve.run(
        build_openai_app(config, num_replicas=n_replicas),
        port=0,
        wait_timeout_s=900,
    )
    port = serve.proxy_port()

    # ByteTokenizer: one token per byte plus BOS.
    short = "The chip is up."  # bucket 32
    long = "A prompt for the second prefill bucket. " * 2  # 81 tokens: 128
    buckets = sorted(
        {
            min(b for b in config.prefill_buckets if b >= len(p) + 1)
            for p in (short, long)
        }
    )
    check(len(buckets) >= 2, f"serve: prompts share one bucket {buckets}")

    def completion(prompt: str, max_tokens: int) -> str:
        out = _post(port, {"prompt": prompt, "max_tokens": max_tokens})
        check("error" not in out, f"serve: {out.get('error')}")
        got = out["usage"]["completion_tokens"]
        check(got == max_tokens,
              f"serve: asked for {max_tokens} tokens, got {got}")
        return out["choices"][0]["text"]

    text_short = completion(short, 16)
    log(f"serve: first completion back ({len(text_short)} chars)")
    completion(long, 24)
    completion(long, 32)
    events = _post_sse(port, {"prompt": short, "max_tokens": 16})
    tail = events[-1]
    check(tail["choices"][0]["finish_reason"] == "stop"
          and tail["usage"]["completion_tokens"] == 16
          and len(events) == 16 + 1,
          f"serve: SSE sent {len(events) - 1} token events, tail {tail}")
    streamed = "".join(e["choices"][0]["text"] for e in events[:-1])
    check(streamed == text_short,
          "serve: the streamed completion differs from the buffered one "
          "for the same prompt under greedy sampling")
    answered = 4

    # Every replica must answer: rounds of distinct concurrent prompts
    # until each engine has generated tokens.
    reports = _replica_reports()
    rounds = 0
    while any(r["stats"]["tokens_generated"] == 0 for r in reports):
        rounds += 1
        check(rounds <= 8,
              "serve: some replica never answered: "
              f"{[r['stats']['tokens_generated'] for r in reports]}")
        prompts = [f"Round {rounds}, request {i}." for i in range(2 * n_replicas)]
        with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
            for fut in [pool.submit(completion, p, 16) for p in prompts]:
                fut.result()
        answered += len(prompts)
        reports = _replica_reports()

    for r in reports:
        log(f"serve: replica {json.dumps(r)}")
        check(r["platform"] == platform,
              f"serve: replica pid {r['pid']} runs on {r['platform']!r} "
              f"({r['device_kind']}), not {platform!r}")
        check(r["stats"]["tokens_generated"] > 0,
              f"serve: replica pid {r['pid']} generated nothing")
    check(len(reports) == n_replicas,
          f"serve: {len(reports)} replicas reported, wanted {n_replicas}")
    chips = [r["visible_chips"] for r in reports]
    check(len(set(chips)) == n_replicas and None not in chips,
          f"serve: replicas do not hold distinct chips: {chips}")
    if platform == "tpu":
        check(all(len(r["device_ids"]) == 1 for r in reports),
              "serve: a num_tpus=1 replica sees "
              f"{[r['device_ids'] for r in reports]}")
    serve.shutdown()
    return {
        "requests_answered": answered,
        "prefill_buckets": buckets,
        "replicas": reports,
    }


# -- driver -------------------------------------------------------------------


def _platform_jax_finds() -> str:
    """Asked of a throwaway process: this one stays off JAX."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; d = jax.devices()[0]; print(d.platform, d.device_kind)"],
        capture_output=True, text=True, timeout=300,
    )
    return out.stdout.strip() or f"nothing ({out.stderr.strip()[-300:]})"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-rehearsal", type=int, nargs="?", const=1, default=0,
        metavar="CHIPS",
        help="debug the script on the CPU at tiny sizes with this many "
        "fake chips (1 or 4); the result is labelled cpu",
    )
    args = ap.parse_args()
    log(f"compile cache: {ensure_compile_cache()}")
    if args.cpu_rehearsal:
        platform = "cpu"
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.cpu_rehearsal}"
        ).strip()
        ray_tpu.init(resources={"TPU": float(args.cpu_rehearsal)})
    else:
        platform = "tpu"
        ray_tpu.init()
    storage = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        n_chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        if n_chips == 0:
            log("FAILED: this host advertises no TPU chip (no /dev/vfio/<n>, "
                f"no /dev/accel*); jax finds: {_platform_jax_finds()}")
            return 1
        log(f"node advertises {ray_tpu.cluster_resources()}")
        meshes = [{"dp": n_chips}]
        if n_chips == 4:
            meshes.append({"fsdp": 2, "tp": 2})
        train = [train_phase(n_chips, m, platform, storage) for m in meshes]
        served = serve_phase(n_chips, platform)
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(storage, ignore_errors=True)
    # Two stdout lines: what every phase reported, then the result.
    print(json.dumps({"train": train, "serve": served}))
    device = {
        "platform": train[0]["platform"],
        "kind": train[0]["device_kind"],
        "count": len(train[0]["device_ids"]),
    }
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
