"""Run one cell of BENCHMARK.json once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time. The cell's configuration, traffic mix and metric
readers are found by name (see README.md); the mix's ``kind`` picks the
driver. Fails, with no result line, without a TPU holding the chips the cell
asks for, unless ``--cpu-rehearsal`` is named: that runs tiny sizes on the
CPU to debug the benchmark's own code, and labels its result ``cpu``.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import harness  # noqa: E402  (first: it notes the process's start)

DRIVERS = {"open-loop": "serve_driver", "closed-loop": "serve_driver", "train": "train_driver"}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", help="directory for this run's logs "
                    "(default: bench_out/<workload>/ in the checkout)")
    ap.add_argument("--cpu-rehearsal", type=int, nargs="?", const=-1, default=0,
                    metavar="CHIPS", help="debug on the CPU at tiny sizes")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(harness.ROOT, "ray_tpu")):
        raise SystemExit("the system under test (ray_tpu/) is not in this checkout")
    cell = harness.cell(args.workload)
    if args.cpu_rehearsal == -1:
        args.cpu_rehearsal = cell["chips"]
    out_dir = os.path.abspath(args.out or os.path.join(
        harness.ROOT, "bench_out", cell["name"], f"seed{args.seed}_trace{args.trace}"
    ))
    shutil.rmtree(os.path.join(out_dir, "trace"), ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    harness.prepare_environment(out_dir, args.cpu_rehearsal)
    kind = harness.traffic_of(cell)["kind"]
    if kind not in DRIVERS:
        raise SystemExit(f"traffic kind {kind!r} has no driver")
    import importlib
    import signal

    def cut(signum, _frame):  # told to stop: leave nothing behind, print no result
        signal.signal(signum, signal.SIG_IGN)  # `timeout` signals the child and its group
        harness.log(f"signal {signum}: ending the run's processes")
        harness.end_run()
        os._exit(128 + signum)

    signal.signal(signal.SIGTERM, cut)
    try:
        return importlib.import_module(f"benchmarks.{DRIVERS[kind]}").run(cell, args, out_dir)
    finally:
        harness.end_run()


if __name__ == "__main__":
    sys.exit(main())
