"""Are the program's outputs right? The program against the plain reference,
over as many seeds as asked, and the controls that must come out wrong.

    python benchmarks/check.py --config <configuration> --seeds 1,2,3 \
        --who program,fp8[,displaced] [--traffic <mix or job>]

What is compared is the family's business (``families/<family>.py``, against
``reference/<family>_ref.py``). ``--who``: ``program`` is the system under
test. ``fp8`` and ``bf16`` put the reference itself, computed in that
precision, in the program's place: the control. A family may add controls of
its own (a displaced cache, for one that serves through block tables). Every
run of a cell makes the ``program`` comparison once, on its own seed, against
the limits in ``benchmarks/limits/<config>.json``; those limits were set from
this script's readings (PERF.md section 2).

Runs in one process on whatever chips JAX finds; without a TPU it fails
unless ``--cpu-rehearsal`` (tiny sizes) is named. The last line of standard
output is one JSON object.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import harness  # noqa: E402


def check_one(config: dict, traffic: dict, seed: int, who: str) -> dict:
    """The compared numbers of one seed, by the configuration's family
    (``families/<family>.py``); ``traffic`` gives the engine settings of a
    serve mix or the mesh and sequence length of a training job."""
    return harness.family(config).check(config, traffic, seed, who)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", help="a mix or job under benchmarks/traffic/; "
                    "default: that of the first cell of this configuration")
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--who", default="program")
    ap.add_argument("--out", help="also write the result here")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()

    from ray_tpu.util.compile_cache import CacheCounter, ensure_compile_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ensure_compile_cache()
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
        ).strip()
    import jax

    cells = [w for w in harness.benchmark()["workloads"] if w["config"] == args.config]
    if not cells:
        raise SystemExit(f"no cell of BENCHMARK.json runs {args.config!r}")
    config = harness.config_of(cells[0])
    traffic = harness.load_json(harness.HERE, "traffic", (args.traffic or cells[0]["traffic"]) + ".json")
    if args.cpu_rehearsal:
        config, traffic = harness.shrink_for_rehearsal(config, traffic)
    devices = jax.devices()
    if devices[0].platform != ("cpu" if args.cpu_rehearsal else "tpu"):
        raise SystemExit(f"JAX finds {devices[0].platform}, not a TPU")
    cache = CacheCounter()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        for who in args.who.split(","):
            row = {"seed": seed, "who": who, **check_one(config, traffic, seed, who)}
            print(json.dumps(row), file=sys.stderr, flush=True)
            rows.append(row)
            jax.clear_caches()  # drop this row's executables and what they hold
            gc.collect()
    stats = [d.memory_stats() or {} for d in devices]
    result = {
        "device": {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0) for s in stats),
        },
        "config": args.config,
        "rows": rows,
        "compile_cache": {"hits": cache.hits, "misses": cache.misses},
    }
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
