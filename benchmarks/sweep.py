"""Find the knee of an open-loop cell: one replica, several offered rates.

    python benchmarks/sweep.py --workload serve-code-mistral7b \
        --rates 1.5,2,2.5,3,3.5,4 --seconds 30 --seed 1

One set-up, then the cell's own mix at each rate in turn (the tables dealt as
in a run, the schedule drawn from the seed). Below the knee the time to the
first token is flat and its two halves agree; above it the second half climbs
because the queue grows all through the run. A cell's ``rate_rps`` is fixed at
about four fifths of the knee, once, by hand, from this output: the benchmark
never searches for a rate. Prints one JSON line per rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import harness, serve_driver, stats  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--cpu-rehearsal", type=int, nargs="?", const=1, default=0)
    args = ap.parse_args()
    cell = harness.cell(args.workload)
    out_dir = os.path.join(harness.ROOT, "bench_out", "sweep")
    os.makedirs(out_dir, exist_ok=True)
    harness.prepare_environment(out_dir, args.cpu_rehearsal)
    config, mix = harness.cell_files(cell, args.cpu_rehearsal)
    ray_tpu, port = serve_driver.deploy(cell, config, mix, args.seed, args.cpu_rehearsal)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            at_rate = {**mix, "rate_rps": rate}
            recs, t0, t1 = serve_driver.run_open_loop(port, at_rate, args.seed, args.seconds)
            ok = [r for r in recs if r["ok"] and r["tokens"]]
            ttft = stats.ttft_ms(ok)
            half = len(ttft) // 2
            last_due = max(r["due"] for r in recs)
            print("sweep " + json.dumps({
                "rate_rps": rate, "requests": len(recs), "ok": len(ok),
                "ttft_p50_ms": stats.percentile(ttft, 50),
                "ttft_p50_first_half": stats.percentile(ttft[:half], 50),
                "ttft_p50_second_half": stats.percentile(ttft[half:], 50),
                "ttft_p90_ms": stats.percentile(ttft, 90),
                "itl_p50_ms": stats.percentile(stats.itl_ms(ok), 50),
                "itl_p90_ms": stats.percentile(stats.itl_ms(ok), 90),
                "itl_p99_ms": stats.percentile(stats.itl_ms(ok), 99),
                "tpot_mean_ms": sum(stats.tpot_ms(ok)) / len(ok),
                "drain_s": t1 - last_due,
            }), flush=True)
    finally:
        serve_driver.teardown(ray_tpu)
    return 0


if __name__ == "__main__":
    sys.exit(main())
