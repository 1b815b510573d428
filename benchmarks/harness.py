"""What every cell's run shares: finding a cell's files by name, the
environment of a run, the readers, and the result line.

Nothing here knows a cell, a configuration, a mix or a metric by name: the
names come from ``BENCHMARK.json`` and each is looked up as a file.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import sys
import time

T_PROCESS_START = time.time()  # imported first thing by run.py
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
_T0 = time.monotonic()
MARK = "RAYTPU_BENCH_RUN_MARK"  # in the environment of every process of a run


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


def note(msg: str) -> None:
    """An earlier line of standard output; the result is the last."""
    print(f"note {msg}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def benchmark() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def cell(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"BENCHMARK.json lists no workload {name!r}")


def config_of(cell_: dict) -> dict:
    for c in benchmark()["configs"]:
        if c["name"] == cell_["config"]:
            return load_json(ROOT, c["file"])
    raise SystemExit(f"BENCHMARK.json lists no config {cell_['config']!r}")


def traffic_of(cell_: dict) -> dict:
    return load_json(HERE, "traffic", cell_["traffic"] + ".json")


def cell_files(cell_: dict, rehearsal: int) -> tuple:
    """(configuration, traffic) of a cell, tiny in a rehearsal."""
    config, traffic = config_of(cell_), traffic_of(cell_)
    return shrink_for_rehearsal(config, traffic) if rehearsal else (config, traffic)


def peaks_for(device_kind: str) -> dict:
    table = load_json(HERE, "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"benchmarks/peaks.json has no device {device_kind!r}")
    return table[device_kind]


def metrics_of(cell_name: str, group: str) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` that this cell reports:
    those that list it, and those that list nothing and so hold everywhere."""
    return [
        m for m in benchmark()[group]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def _module(group_dir: str, name: str):
    """``benchmarks/<group_dir>/<name>.py`` as a module, found by name."""
    path = os.path.join(HERE, group_dir, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{group_dir}.{name.replace('.', '_').replace('-', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(group_dir: str, name: str):
    """``benchmarks/<group_dir>/<name>.py``, which holds ``read(records)``."""
    return _module(group_dir, name).read


def family(config: dict):
    """``benchmarks/families/<config["family"]>.py``: the builder, the check,
    the rehearsal sizes and the operation counts of the configuration's
    family, as far as its cells need them (README.md lists the interface)."""
    return _module("families", config["family"])


def read_metrics(cell_name: str, trace: bool, records: dict) -> dict:
    """Each listed metric through its reader. A reader that finds nothing to
    read returns None and its metric is left out of the line."""
    group, group_dir = (
        ("per_layer", "layer_metrics") if trace else ("end_to_end", "end_to_end")
    )
    out = {}
    for m in metrics_of(cell_name, group):
        got = reader(group_dir, m["name"])(records)
        if got is None:
            continue
        value, unit = got
        if unit != m["unit"]:
            raise SystemExit(f"{m['name']}: reader gives {unit!r}, not {m['unit']!r}")
        out[m["name"]] = {"value": float(value), "unit": unit}
    return out


def prepare_environment(out_dir: str, rehearsal_chips: int) -> None:
    """Before ``ray_tpu`` or ``jax`` is imported. Workers inherit it all."""
    # While a worker opens its chips the node in this process sends the GCS
    # in this process no heartbeat for 2.4-9.0 s in a serve cell and up to
    # 11 s in the four-chip cell (every run's flight-recorder dump, my chip
    # runs, PR 28). Past the default 10 s the GCS declares its own node dead
    # and every actor on it: the train cell shrugs that off, a serve cell
    # waits for a deployment that never comes up until the run is cut. One
    # host has no node to lose, so the run gives the silence two minutes.
    os.environ.setdefault("RAY_TPU_NODE_DEATH_TIMEOUT_S", "120")
    from ray_tpu.util.compile_cache import ensure_compile_cache

    os.environ["PYTHONPATH"] = os.pathsep.join(
        [ROOT, *filter(None, [os.environ.get("PYTHONPATH")])]
    )
    os.environ[MARK] = f"{os.getpid()}.{time.time_ns()}"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["RAY_TPU_FLIGHTREC_DUMP_DIR"] = os.path.join(out_dir, "flightrec_dumps")
    os.environ.setdefault("RAY_TPU_FLIGHTREC_RING_SIZE", "65536")
    os.environ["RAY_TPU_STORAGE_PATH"] = os.path.join(out_dir, "train_storage")
    if rehearsal_chips:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={rehearsal_chips}"
        ).strip()
    log(f"compile cache: {ensure_compile_cache()}; log: {out_dir}")


def start_runtime(chips: int, rehearsal_chips: int):
    """The cluster runtime of this run; fails where the node advertises
    fewer TPU chips than the cell asks for. A rehearsal fakes them, on the
    CPU, and only when named on the command line."""
    import ray_tpu

    log("program imported")
    if rehearsal_chips:
        ray_tpu.init(resources={"TPU": float(rehearsal_chips)})
    else:
        ray_tpu.init()
    have = int(ray_tpu.cluster_resources().get("TPU", 0))
    from ray_tpu.core.config import GLOBAL_CONFIG

    log(f"runtime up, {have} TPU chip(s) advertised; a node is dead after "
        f"{GLOBAL_CONFIG.node_death_timeout_s:.0f} s of silence")
    if have < chips:
        ray_tpu.shutdown()
        raise SystemExit(f"this host advertises {have} TPU chip(s); the cell needs {chips}")
    return ray_tpu


def check_device(device: dict, chips: int, rehearsal_chips: int) -> None:
    want = "cpu" if rehearsal_chips else "tpu"
    if device["platform"] != want or device["count"] < chips:
        raise SystemExit(
            f"ran on {device['count']} x {device['platform']} ({device['kind']}); "
            f"the cell needs {chips} x {want}"
        )


def compared(name: str, value: float, limit: float) -> bool:
    """Print one compared number beside its limit; True when inside it."""
    ok = value <= limit
    print(f"compared {name}: {value:.6g} limit {limit:g} {'ok' if ok else 'OVER'}", flush=True)
    return ok


def result_line(correct, attempted, failed, metrics, device, breakdown=None) -> None:
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown:
        line["breakdown"] = breakdown
    print(json.dumps(line), flush=True)


def save(out_dir: str, name: str, obj) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(obj, f)


def shrink_for_rehearsal(config: dict, traffic: dict) -> tuple:
    """Tiny sizes for a run on the CPU that only debugs the benchmark's own
    code (``--cpu-rehearsal``): the configuration by its family's file, the
    mix by its kind, never by name."""
    c, t = family(config).shrink(config), json.loads(json.dumps(traffic))
    if t["kind"] == "train":
        t.update(seq_len=128, global_batch=4, attn_impl="reference")
        t["trace_window"] = {"seconds": 1.0}
    else:
        t["prompt_tokens"] = [max(9, p // 16) for p in t["prompt_tokens"]]
        t["output_tokens"] = [max(2, o // 8) for o in t["output_tokens"]]
        t["engine"].update(max_seq=256, num_kv_blocks=16 * 16 + 1,
                           prefill_buckets=[16, 32, 64, 128])
        t["trace_window"] = {"start_s": 1.0, "seconds": 1.5}
    return c, t


def reduce_trace(trace_dir: str, anchor_wall_ns, spans: list, out_dir: str, rehearsal: int):
    """The traced process's profile reduced (trace_reduce.py), its idle gaps
    named after the host spans laid over them by the clock anchor. A CPU
    rehearsal has no device plane to reduce and gets None."""
    from benchmarks import trace_reduce

    plain = trace_reduce.plain_from_xplane(trace_reduce.find_xplane(trace_dir))
    offset = trace_reduce.clock_offset_ns(plain, anchor_wall_ns or 0)
    note(f"trace: planes {[p['name'] for p in plain['planes']]}; clock offset to wall {offset} ns")
    on_trace_clock = [
        (s["phase"], int(s["t"] * 1e9) - offset, int((s["t"] + s["dur_s"]) * 1e9) - offset)
        for s in spans if s["dur_s"] > 0
    ] if offset is not None else []
    try:
        reduced = trace_reduce.reduce(plain, on_trace_clock)
    except ValueError as e:
        if rehearsal:
            note(f"trace not reduced in a rehearsal: {e}")
            return None
        raise SystemExit(f"trace: {e}")
    reduced["offset_ns"] = offset
    reduced["t0_wall"] = (reduced["t0_ns"] + offset) / 1e9 if offset is not None else None
    save(out_dir, "trace_reduced.json", reduced)
    return reduced


def device_line(device: dict, reduced) -> tuple:
    """(the result line's ``device``, its ``breakdown``)."""
    from benchmarks import trace_reduce

    out = {k: device[k] for k in ("platform", "kind", "count", "memory_peak_bytes")}
    if reduced is None:
        return out, None
    out["busy_s"], out["window_s"] = reduced["busy_s"], reduced["window_s"]
    return out, trace_reduce.breakdown(reduced)


def _proc_table() -> dict:
    """pid -> (parent pid, state, threads) of every process, from /proc."""
    table = {}
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                rest = f.read().rsplit(")", 1)[1].split()
            table[int(name)] = (int(rest[1]), rest[0], int(rest[17]))
        except (OSError, IndexError, ValueError):
            continue  # gone while we looked
    return table


def descendants() -> set:
    """Every process this one has started, directly or not, that still runs."""
    table = _proc_table()
    found, frontier = set(), {os.getpid()}
    while frontier:
        frontier = {p for p, row in table.items() if row[0] in frontier} - found
        found |= frontier
    return found


def _ended(pid: int, table: dict) -> bool:
    """Whether ``pid`` holds nothing any more. A child of this process has
    ended when the kernel lets it be reaped, which it does only once every
    thread of it is gone. Another's child is judged by /proc: gone, or a
    zombie that counts no thread but its first."""
    if pid not in table:
        return True
    try:
        return os.waitpid(pid, os.WNOHANG)[0] == pid
    except ChildProcessError:
        pass
    _parent, state, threads = table[pid]
    try:
        threads = max(threads, len(os.listdir(f"/proc/{pid}/task")))
    except OSError:
        pass
    return state == "Z" and threads <= 1


def marked() -> set:
    """Every other process that carries this run's mark in its environment
    (``prepare_environment`` sets it, and whatever the run starts inherits
    it): also one whose parent has died, which ``descendants`` cannot see."""
    want = f"{MARK}={os.environ.get(MARK)}".encode()
    found = set()
    for name in os.listdir("/proc"):
        if not name.isdigit() or int(name) == os.getpid():
            continue
        try:
            with open(f"/proc/{name}/environ", "rb") as f:
                if want in f.read().split(b"\0"):
                    found.add(int(name))
        except OSError:
            continue  # gone, or not ours to read
    return found


def run_processes() -> set:
    """Every process of this run that still runs, but this one."""
    return descendants() | marked()


def _cmdline(pid: int) -> str:
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return f.read().replace(b"\0", b" ").decode(errors="replace").strip()[:160]
    except OSError:
        return "?"


def wait_until_ended(pids: set, patience_s: float = 90.0, more=None) -> None:
    """Wait until each of ``pids`` has ended. A zombie whose threads still
    end has not: a killed worker that held four chips stays one for some
    15 s while its mappings are taken apart on three cores, and a process
    started meanwhile imports jax in 10 s instead of 3 (my chip runs, PR
    25), so the next run's set-up paid for this run's end. The next process
    to open the chip must not meet the old one there either. What outlives
    the patience is killed and waited for. ``more`` is asked on every turn
    for processes that were not in ``pids``: one started while the runtime
    stopped, or one that lost its parent before ``pids`` was taken. Nothing
    stops those any more, so they are killed at once, named in the log, and
    waited for like the others."""
    import signal

    t = time.time()
    pids, late, killed = set(pids), set(), set()
    while True:
        if more is not None:
            new = more() - pids - late
            for p in new:
                log(f"process {p} was not stopped with the runtime: {_cmdline(p)}")
            late |= new
        table = _proc_table()
        alive = {p for p in pids | late if not _ended(p, table)}
        if not alive:
            break
        overdue = alive if time.time() - t > patience_s else alive & late
        for p in overdue - killed:
            try:
                os.kill(p, signal.SIGKILL)
            except OSError:
                pass
        killed |= overdue
        if time.time() - t > patience_s + 120.0:
            log(f"gave up waiting for {sorted(alive)}: killed and still there")
            break
        time.sleep(0.25)
    if pids or late:
        log(f"{len(pids | late)} process(es) of the run ended {time.time() - t:.1f}s "
            f"after the runtime stopped ({len(late)} of them killed here)")


def end_run() -> None:
    """The last thing a run does, on every path out of it: whatever it
    started and has not seen end is killed and waited for."""
    wait_until_ended(set(), patience_s=0.0, more=run_processes)
