"""Device time by stage: every operation of a traced run filed under the stage
its scope path names, a program at a time.

The program opens one fixed vocabulary of stages as ``jax.named_scope``s
(``ray_tpu/models/common.py:stage``: ``st.attn_proj``, ``st.state_scan`` ...),
so an operation's HLO metadata says which part of a layer it belongs to
(``op_name="jit(paged_prefill)/jit(main)/while/body/st.state_in/dot_general"``,
and ``transpose(jvp(st.mlp))`` in a backward pass). ``trace_reduce`` keeps an
event's short name alone; this module opens the run's ``.xplane.pb`` itself and
keeps the path too.

**Where the path lives on a TPU** (found on the chip, PR 53). Not in the
event's name: an ``XLA Ops`` event is named by its whole HLO line, and the
line is printed without ``metadata={op_name=...}``. Not in the event's
statistics: ``jax.profiler.ProfileData`` gives an event ``device_offset_ps``,
``device_duration_ps`` and a time scale, no more. It is the ``tf_op``
statistic of the event's *metadata* (``XEventMetadata.stats``, beside
``program_id``, ``flops``, ``source``: one record an instruction of a
program, which every event of that instruction points at by id), and
``ProfileData`` does not surface an event's metadata. So this module reads
the protobuf's wire format itself, the few fields it needs
(``_device_plane``), with nothing but the standard library; an event still
finds its path through its own metadata id, so two programs' instructions of
one name stay two.

Attribution is per event, not through a table of names: ``fusion.130`` of the
decode program and ``fusion.130`` of a prefill bucket are different
instructions. The nesting is flattened with ``trace_reduce.leaves`` (a
``while`` does not count its body twice), each piece goes to the run on ``XLA
Modules`` that contains its start, and so to a program by the program's name. A
fusion goes whole to the stage its own metadata names (one that spans two
stages is filed under one of them: the blur is a fusion wide); where a path
holds several stages the innermost of the last entry counts.

``unnamed`` is everything of a program's runs that no stage names: operations
whose path holds no stage, and the time inside a run in which no operation ran
(``between_ops``). So a program's stage shares and its unnamed share add up to
100 by construction, over the seconds of the program's runs in the window, the
denominator the older shares of device time have.
"""

from __future__ import annotations

import bisect
import os
import re
import time

from benchmarks import harness, trace_reduce

STAGE = re.compile(r"\bst\.([a-z_]+)")
PATH_STAT = "tf_op"
PREFILL, DECODE = "jit_paged_prefill", "jit_paged_decode"
TICKS_A_SECOND = 1e12  # a trace's times are picoseconds


def stage_of(path: str):
    """The stage a scope path names (the innermost, of the last entry where
    the compiler merged several with ``;``), or None."""
    found = STAGE.findall(path)
    return found[-1] if found else None


def _varint(buf, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message in wire format: an
    int for a varint, a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i : i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i : i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an .xplane.pb")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode(errors="replace")


def _map_entry(entry) -> tuple:
    """(key, value) of one entry of a protobuf map."""
    key = value = None
    for number, x in _fields(entry):
        if number == 1:
            key = x
        elif number == 2:
            value = x
    return key, value


def _device_plane(plane):
    """``{"name", "ops", "modules"}`` of one ``XPlane`` in wire format, or None
    for a plane that is no TPU's. Field numbers are those of tsl's ``xplane.proto``:
    XPlane 2 name, 3 lines, 4 event_metadata, 5 stat_metadata; XLine 2 name, 3
    timestamp_ns, 4 events; XEvent 1 metadata_id, 2 offset_ps, 3 duration_ps;
    XEventMetadata 2 name, 5 stats; XStat 1 metadata_id, 5 str_value, 7
    ref_value (a string kept once, as the name of a stat's metadata)."""
    name, lines, event_metadata, stat_names = "", [], [], {}
    for number, x in _fields(plane):
        if number == 2:
            name = _text(x)
        elif number == 3:
            lines.append(x)
        elif number == 4:
            event_metadata.append(x)
        elif number == 5:
            key, value = _map_entry(x)
            stat_names[key] = next((_text(v) for n, v in _fields(value) if n == 2), "")
    if not name.startswith("/device:TPU:"):
        return None
    instruction = {}  # an event's metadata id -> (short name, stage): the path is said once an instruction
    for entry in event_metadata:
        key, value = _map_entry(entry)
        long_name, path = "", ""
        for number, x in _fields(value):
            if number == 2:
                long_name = _text(x)
            elif number == 5:
                stat = dict(_fields(x))
                if stat_names.get(stat.get(1)) == PATH_STAT:
                    path = _text(stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
        instruction[key] = (trace_reduce.short_name(long_name), stage_of(path))
    ops, modules = [], []
    for line in lines:
        line_name, t0_ps, events = "", 0, []
        for number, x in _fields(line):
            if number == 2:
                line_name = _text(x)
            elif number == 3:
                t0_ps = 1000 * x
            elif number == 4:
                events.append(x)
        if line_name not in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE):
            continue
        for event in events:
            found = {number: x for number, x in _fields(event) if number <= 3}
            short, stage = instruction.get(found.get(1), ("?", None))
            start, dur = t0_ps + found.get(2, 0), found.get(3, 0)
            if line_name == trace_reduce.OPS_LINE:
                ops.append([short, start, dur, stage])
            else:
                modules.append([short, start, dur])
    return {"name": name, "ops": ops, "modules": modules}


def plain_from_xplane(path: str) -> dict:
    """``{"planes": [{"name", "ops": [[short name, start, length, stage or
    None], ...], "modules": [[name, start, length], ...]}]}`` of the device
    planes, times in picoseconds: what ``stage_times`` works on, so that it
    can be checked on a hand-made trace with no profiler."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for number, plane in _fields(space):  # XSpace 1 planes
        found = _device_plane(plane) if number == 1 else None
        if found is not None:
            planes.append(found)
    return {"planes": planes}


def stage_times(plain: dict) -> dict:
    """``{program name: {"runs", "total_s", "stages": {stage: s}, "unnamed_s",
    "between_ops_s", "unnamed_top": [[operation, s], ...], "longest":
    [[operation, stage, s], ...]}}``: seconds averaged over the devices,
    ``runs`` those of the first device. ``unnamed_s`` is ``total_s`` less the
    stages' seconds: the operations without a stage, of which ``unnamed_top``
    names the five longest by their own time, and ``between_ops_s``, the time
    of the runs in which no operation ran. ``longest`` says of the program's
    sixteen longest operations which stage each is: a fusion number means
    something again."""
    n = len(plain["planes"])
    seconds = lambda ticks: ticks / n / TICKS_A_SECOND  # noqa: E731
    out: dict = {}

    def program(name):
        return out.setdefault(
            name, {"runs": 0, "total": 0, "stages": {}, "staged_ops": 0, "unnamed_ops": {}, "staged": {}}
        )

    for d, plane in enumerate(plain["planes"]):
        runs = sorted(plane["modules"], key=lambda r: r[1])
        starts = [r[1] for r in runs]
        for name, _s, dur in runs:
            p = program(name)
            p["total"] += dur
            p["runs"] += d == 0
        ops = plane["ops"]
        for i, a, b in trace_reduce.leaves([[i, s, dur] for i, (_n, s, dur, _st) in enumerate(ops)]):
            k = bisect.bisect_right(starts, a) - 1
            if k < 0 or a >= runs[k][1] + runs[k][2]:
                continue  # no run of a program holds it
            p = program(runs[k][0])
            name, _s, _d, stage = ops[i]
            if stage is None:
                p["unnamed_ops"][name] = p["unnamed_ops"].get(name, 0) + (b - a)
            else:
                p["stages"][stage] = p["stages"].get(stage, 0) + (b - a)
                p["staged"][name, stage] = p["staged"].get((name, stage), 0) + (b - a)
                p["staged_ops"] += 1
    for p in out.values():
        staged = sum(p["stages"].values())
        unnamed = p.pop("unnamed_ops")
        p["total_s"] = seconds(p.pop("total"))
        p["stages"] = {k: seconds(v) for k, v in sorted(p["stages"].items())}
        p["unnamed_s"] = p["total_s"] - seconds(staged)
        p["between_ops_s"] = max(0.0, p["unnamed_s"] - seconds(sum(unnamed.values())))
        p["unnamed_top"] = _longest({k: seconds(v) for k, v in unnamed.items()})
        every = {**p.pop("staged"), **{(k, None): v for k, v in unnamed.items()}}
        p["longest"] = [[*k, v] for k, v in _longest({k: seconds(v) for k, v in every.items()}, 16)]
    return out


def _longest(seconds: dict, top: int = 5) -> list:
    return sorted(([k, v] for k, v in seconds.items()), key=lambda kv: -kv[1])[:top]


def merged(programs: dict, names: list) -> dict | None:
    """The programs of ``names`` as one (a prefill program a bucket), or None
    where none of them ran. An operation's name means nothing outside its
    program, so among several the longest unnamed are said with theirs."""
    parts = [programs[name] for name in names]
    if not parts:
        return None
    stages: dict = {}
    top: dict = {}
    for name, p in zip(names, parts):
        for k, v in p["stages"].items():
            stages[k] = stages.get(k, 0.0) + v
        for k, v in p["unnamed_top"]:
            top[k if len(parts) == 1 else f"{k} of {name}"] = v
    return {
        "programs": sorted(names),
        "runs": sum(p["runs"] for p in parts),
        "total_s": sum(p["total_s"] for p in parts),
        "stages": dict(sorted(stages.items())),
        "staged_ops": sum(p["staged_ops"] for p in parts),
        "unnamed_s": sum(p["unnamed_s"] for p in parts),
        "between_ops_s": sum(p["between_ops_s"] for p in parts),
        "unnamed_top": _longest(top),
    }


def by_kind(programs: dict) -> dict:
    """``{"prefill", "decode", "train"}`` -> the merged programs of that kind,
    found by name: the engine's two by theirs, and for training the program
    that takes most of the device's time where the engine's do not run."""
    prefill = [name for name in programs if name.startswith(PREFILL)]
    decode = [name for name in programs if name.startswith(DECODE)]
    kinds = {"prefill": merged(programs, prefill), "decode": merged(programs, decode)}
    if not prefill and not decode and programs:
        kinds["train"] = merged(programs, [max(programs, key=lambda name: programs[name]["total_s"])])
    return {k: v for k, v in kinds.items() if v is not None}


def of_run(records) -> dict | None:
    """The traced run's stage times by kind of program, read once a run (the
    readers share it through ``records``) from the ``.xplane.pb`` under the
    run's directory, written beside it as ``stage_times.json`` and as a
    ``note`` line a kind. None without a trace."""
    if "stage_times" not in records:
        records["stage_times"] = _read_run(records)
    return records["stage_times"]


def _read_run(records) -> dict | None:
    if records.get("trace") is None or "RAY_TPU_FLIGHTREC_DUMP_DIR" not in os.environ:
        return None
    # harness.prepare_environment puts the flight recorder's dumps in the run's directory
    run_dir = os.path.dirname(os.environ["RAY_TPU_FLIGHTREC_DUMP_DIR"])
    t = time.time()
    try:
        path = trace_reduce.find_xplane(os.path.join(run_dir, "trace"))
    except FileNotFoundError:
        return None
    programs = stage_times(plain_from_xplane(path))
    kinds = by_kind(programs)
    pass_s = time.time() - t
    harness.save(run_dir, "stage_times.json", {
        "xplane": path, "xplane_bytes": os.path.getsize(path), "pass_s": pass_s,
        "kinds": kinds, "programs": programs,
    })
    harness.note(f"stage times: one pass over {os.path.getsize(path)} B of trace in {pass_s:.2f} s")
    for kind, p in kinds.items():
        pct = lambda seconds: round(100 * seconds / p["total_s"], 2) if p["total_s"] else 0.0  # noqa: E731
        harness.note(
            f"stage times {kind}: {p['runs']} runs, {p['total_s']:.4f} s a device; "
            f"% by stage {({k: pct(v) for k, v in p['stages'].items()})}; unnamed {pct(p['unnamed_s'])}% "
            f"of which between operations {pct(p['between_ops_s'])}%; longest unnamed (s) "
            f"{[[k, round(v, 4)] for k, v in p['unnamed_top']]}"
        )
    return kinds


def share(records, kind: str, stage: str | None):
    """100 x the seconds of ``stage`` (None: of ``unnamed``) over the seconds
    of the runs of the programs of ``kind`` in the traced window. None where
    the trace holds no run of such a program or no staged operation at all (a
    commit from before the stages); 0.0 for a stage the program spent nothing
    in."""
    kinds = of_run(records)
    if not kinds or kind not in kinds:
        return None
    p = kinds[kind]
    if not p["staged_ops"] or not p["total_s"]:
        return None
    seconds = p["unnamed_s"] if stage is None else p["stages"].get(stage, 0.0)
    return 100.0 * seconds / p["total_s"], "%"
