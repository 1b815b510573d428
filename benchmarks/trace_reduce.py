"""From a profiler trace to busy time, idle gaps, operations and programs.

``plain_from_xplane`` turns the profiler's ``.xplane.pb`` into a plain dict
(``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
dur_ns], ...]}]}]}``); ``reduce`` works on that dict alone, so it can be
checked on the small recorded trace under ``tests/data/`` with no profiler.

Device planes are those named ``/device:TPU:<n>``. On each, the line ``XLA
Ops`` holds the operations (nested: a ``while`` contains its body's ops) and
``XLA Modules`` one event per run of a compiled program.
"""

from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CLOCK_ANCHOR = "bench_clock_anchor"
COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)


def find_xplane(log_dir: str) -> str:
    paths = sorted(
        glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def plain_from_xplane(path: str, keep_host_events=(CLOCK_ANCHOR,)) -> dict:
    """Device planes whole; of the host planes only the named events (they
    carry the clock anchor), since host lines can run to millions of events."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = [
                [short_name(e.name), int(e.start_ns), int(e.duration_ns)]
                for e in line.events
                if device or e.name in keep_host_events
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def short_name(name: str) -> str:
    """The TPU trace names an op by its whole HLO line (``%copy.41 = bf16[...]
    copy(...)``); keep the instruction's name."""
    if name.startswith("%"):
        return name[1:].split(" ", 1)[0]
    return name


def _is_device(plane: dict) -> bool:
    return plane["name"].startswith("/device:TPU:")


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _length(intervals: list) -> int:
    return sum(b - a for a, b in intervals)


def _subtract(intervals: list, holes: list) -> list:
    """``intervals`` minus ``holes`` (both merged and sorted)."""
    out = []
    j = 0
    for a, b in intervals:
        cur = a
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > cur:
                out.append([cur, holes[k][0]])
            cur = max(cur, holes[k][1])
            k += 1
        if cur < b:
            out.append([cur, b])
    return out


def leaves(events: list) -> list:
    """The nesting flattened: ``(name, start, end)`` pieces in which the named
    op is the innermost one running."""
    out: list = []
    stack: list = []  # [name, end, cursor]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, cur = stack.pop()
            if end > cur:
                out.append((name, cur, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack and start > stack[-1][2]:
            out.append((stack[-1][0], stack[-1][2], start))
        stack.append([name, start + dur, start])
    close(float("inf"))
    return out


def self_times(events: list) -> dict:
    """Name -> nanoseconds in the op itself, its nested children taken out."""
    total: dict = {}
    for name, a, b in leaves(events):
        total[name] = total.get(name, 0) + (b - a)
    return total


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def clock_offset_ns(trace: dict, anchor_wall_ns: int):
    """What to add to a trace time to get wall time, from the anchor event
    that the traced process wrote at a known wall time; None without it."""
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for name, start, _dur in line["events"]:
                if name == CLOCK_ANCHOR:
                    return anchor_wall_ns - start
    return None


def _label_gap(a: int, b: int, modules: list, spans: list) -> str:
    mid = (a + b) // 2
    if any(s <= mid < e for s, e in modules):
        return "between_ops_of_a_running_program"
    best = None
    for name, s, e in spans:
        if s <= mid < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "host_outside_any_span"


def reduce(trace: dict, spans=()) -> dict:
    """``spans`` are ``(name, start_ns, end_ns)`` of host phases already on
    the trace's clock; idle gaps are named after the shortest span that
    covers their middle. Times come back in seconds, averaged over devices
    where a sum over devices would not mean anything."""
    devices = [p for p in trace["planes"] if _is_device(p)]
    if not devices:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    spans = list(spans)
    busy_ns = window_ns = coll_ns = exposed_ns = 0
    op_ns: dict = {}
    gap_ns: dict = {}
    starts, ends = [], []
    for plane in devices:
        for _n, s, d in _line(plane, OPS_LINE):
            starts.append(s)
            ends.append(s + d)
    if not starts:
        raise ValueError("no operation ran on the device in this trace")
    t0, t1 = min(starts), max(ends)
    for plane in devices:
        ops = _line(plane, OPS_LINE)
        busy = _union([[s, s + d] for _n, s, d in ops])
        modules = [[s, s + d] for _n, s, d in _line(plane, MODULES_LINE)]
        busy_ns += _length(busy)
        window_ns += t1 - t0
        for a, b in _subtract([[t0, t1]], busy):
            label = _label_gap(a, b, modules, spans)
            gap_ns[label] = gap_ns.get(label, 0) + (b - a)
        for name, ns in self_times(ops).items():
            op_ns[name] = op_ns.get(name, 0) + ns
        # Collectives may sit on the ops line or on lines of their own
        # (asynchronous pairs); compute is every other innermost op.
        coll, compute = [], []
        for name, a, b in leaves(ops):
            (coll if is_collective(name) else compute).append([a, b])
        for line in plane["lines"]:
            if line["name"] not in (OPS_LINE, MODULES_LINE):
                coll.extend(
                    [s_, s_ + d_] for name, s_, d_ in line["events"]
                    if is_collective(name)
                )
        coll = _union(coll)
        coll_ns += _length(coll)
        exposed_ns += _length(_subtract(coll, _union(compute)))
    n = len(devices)
    runs = sorted(_line(devices[0], MODULES_LINE), key=lambda e: e[1])
    programs: dict = {}
    for name, _s, d in runs:
        programs.setdefault(name, []).append(d / 1e9)
    rank = lambda d: sorted(  # noqa: E731
        ([k, v / n / 1e9] for k, v in d.items()), key=lambda kv: -kv[1]
    )
    return {
        "devices": n,
        "t0_ns": t0,
        "busy_s": busy_ns / n / 1e9,
        "window_s": window_ns / n / 1e9,
        "ops": rank(op_ns),
        "idle_gaps": rank(gap_ns),
        "programs": programs,
        "program_runs": [list(e) for e in runs],  # device 0: name, start, length (ns)
        "collective_s": coll_ns / n / 1e9,
        "collective_exposed_s": exposed_ns / n / 1e9,
    }


def runs_of_phase(reduced: dict, spans: list, phase: str, min_ns: int = 1_000_000) -> list:
    """Device seconds of every run of the programs that a host phase starts.

    Programs jitted from a ``functools.partial`` are all named
    ``jit__unknown(<hash>)`` in the trace, so they are told apart by what the
    host was doing: the first run of at least ``min_ns`` (shorter ones are
    scalar conversions) that starts after a span of ``phase`` starts, and
    within 50 ms of it, is a program of that phase. ``spans`` are on the wall
    clock; ``reduced["offset_ns"]`` takes the trace's clock to it."""
    import bisect

    if reduced.get("offset_ns") is None:
        return []
    runs = [r for r in reduced["program_runs"] if r[2] >= min_ns]
    starts = [r[1] for r in runs]
    names = set()
    for s in spans:
        if s["phase"] != phase:
            continue
        at = int(s["t"] * 1e9) - reduced["offset_ns"]
        i = bisect.bisect_left(starts, at)
        if i < len(runs) and starts[i] - at < 50_000_000:
            names.add(runs[i][0])
    return [r[2] / 1e9 for r in runs if r[0] in names]


def breakdown(reduced: dict, top: int = 10) -> dict:
    return {
        "device_ops": reduced["ops"][:top],
        "idle_gaps": reduced["idle_gaps"][:top],
    }
