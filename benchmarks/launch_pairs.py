"""Readings of the engine's admitting turns and of its numbered launches,
shared by the per-layer metrics that are a line over them (one file per
metric name under ``layer_metrics/``).

An admitting turn records one ``llm.admit_wave`` span with what it counted
(``waiting``, ``left``, ``admitted``, ``prefills``, ``tokens``, ``padded``,
``reused``, ``rows_stalled``, ``inflight_age_ms``); the first four readers
are sums over the waves that start inside the measured window.

Every prefill and decode program the engine launches takes the block pool
from the launch before it, so the device runs them in launch order, and the
engine numbers the launches: ``llm.prefill`` / ``llm.prefill_chunk`` carry
``seq``, ``llm.decode_step`` carries ``seq`` (the step it read) and
``next_seq`` (the step it launched ahead, 0 where none). ``pairs`` lays those
numbers on the runs of the two programs in a reduced trace by counting; the
clock anchor only guesses the first run's number, and the guess is checked
without any clock. Everything here returns None where the program records no
such span or field, as a commit from before them does.
"""

from __future__ import annotations

import bisect
import collections

from benchmarks import stats

WAVE, STEP = "llm.admit_wave", "llm.decode_step"
PREFILLS = ("llm.prefill", "llm.prefill_chunk")
PROGRAMS = {"jit_paged_prefill": "prefill", "jit_paged_decode": "decode"}
Run = collections.namedtuple("Run", "kind start_ns dur_ns")


def waves(records) -> list:
    """What each admitting turn of the measured window counted, with the
    span's length as ``dur_s``."""
    return [
        {**s["extra"], "dur_s": s["dur_s"]}
        for s in stats.spans_in(records["spans"], WAVE, *records["window"])
    ]


def wave_ms_p50(records):
    got = waves(records)
    if not got:
        return None
    return stats.percentile([w["dur_s"] * 1e3 for w in got], 50), "ms"


def wave_time_pct(records):
    """Seconds inside admitting turns over the window's, from spans alone:
    the prefills, the wait for the decode step in flight before them, the
    first samples and the books, with every decode stopped."""
    got = waves(records)
    if not got:
        return None
    t0, t1 = records["window"]
    return 100.0 * sum(w["dur_s"] for w in got) / (t1 - t0), "%"


def batchable_pct(records):
    """Of the requests admitted in the window, the share admitted in a wave
    of two or more: what a prefill that batches the prompts waiting together
    could merge."""
    got = waves(records)
    admitted = sum(w["admitted"] for w in got)
    if not admitted:
        return None
    return 100.0 * sum(w["admitted"] for w in got if w["admitted"] >= 2) / admitted, "%"


def pad_pct(records):
    """Of the rows the window's prefill programs computed (the sum of their
    buckets), the share that was padding."""
    got = waves(records)
    padded = sum(w["padded"] for w in got)
    if not padded:
        return None
    return 100.0 * (padded - sum(w["tokens"] for w in got)) / padded, "%"


def _kind(name: str):
    for program, kind in PROGRAMS.items():
        if name.startswith(program):
            return kind
    return None


def _ns(records, wall_s: float) -> int:
    """A wall-clock time on the trace's clock, by the anchor's offset."""
    return int(wall_s * 1e9) - records["trace"]["offset_ns"]


def pairs(records):
    """``{launch number: Run}`` for every run of the engine's two programs
    in the trace, or None.

    The runs in start order are consecutive launch numbers, so one number is
    unknown: the first run's. The guess: a decode step's tokens are read a
    fraction of a millisecond after its run ends, so the run a
    ``llm.decode_step`` span read is the last decode run to end by the span's
    end, give or take half a decode run for the clocks (the anchor errs by
    2 ms or less, decode runs last 7.8 ms or more); every span of the traced
    seconds votes and the commonest answer wins. The check needs no clock:
    along the whole trace every ``seq`` of a prefill span must land on a
    prefill run and every ``seq`` / ``next_seq`` of a decode span on a decode
    run; one that does not means the count is off, and nothing is returned
    rather than a wrong pairing."""
    trace = records["trace"]
    if trace is None or trace.get("offset_ns") is None:
        return None
    runs = [
        Run(kind, start, dur)
        for name, start, dur in trace["program_runs"] if (kind := _kind(name))
    ]
    want, steps, agree = {}, [], True
    for s in records["spans"]:
        x = s["extra"]
        if "seq" not in x:
            continue
        if s["phase"] in PREFILLS:
            agree &= want.setdefault(x["seq"], "prefill") == "prefill"
        elif s["phase"] == STEP:
            steps.append(s)
            for n in filter(None, (x["seq"], x["next_seq"])):
                agree &= want.setdefault(n, "decode") == "decode"
    decodes = [(i, r.start_ns + r.dur_ns) for i, r in enumerate(runs) if r.kind == "decode"]
    if not decodes or not steps or not agree:
        return None
    ends = [end for _i, end in decodes]
    slack = min(r.dur_ns for r in runs if r.kind == "decode") // 2
    votes: collections.Counter = collections.Counter()
    for s in steps:
        at = _ns(records, s["t"] + s["dur_s"])
        j = bisect.bisect_right(ends, at + slack) - 1
        if j >= 0 and at - slack <= ends[-1]:
            votes[s["extra"]["seq"] - decodes[j][0]] += 1
    if not votes:
        return None
    first = votes.most_common(1)[0][0]
    got = {first + i: run for i, run in enumerate(runs)}
    if any(want.get(seq, run.kind) != run.kind for seq, run in got.items()):
        return None
    return got


def admit_device_idle_ms_p50(records):
    """Median, over the admitting turns of the traced seconds, of the
    device's idle time around a wave's prefills: from the end of the last
    decode run before the wave's first prefill run to the start of the first
    decode run after its last, less whatever program ran in between (the
    prefills themselves). Runs are found by launch number, not by clock; a
    wave at the trace's edge, without a decode run on both sides, is left
    out."""
    got = pairs(records)
    if got is None:
        return None
    by_wave: dict = {}
    for s in records["spans"]:
        x = s["extra"]
        if s["phase"] in PREFILLS and x.get("seq") in got and "wave" in x:
            by_wave.setdefault(x["wave"], []).append(x["seq"])
    every = [(start, start + dur) for _name, start, dur in records["trace"]["program_runs"]]
    idle = []
    for seqs in by_wave.values():
        before, after = min(seqs) - 1, max(seqs) + 1
        while before in got and got[before].kind != "decode":
            before -= 1
        while after in got and got[after].kind != "decode":
            after += 1
        if before not in got or after not in got:
            continue
        a = got[before].start_ns + got[before].dur_ns
        b = got[after].start_ns
        busy = sum(min(e, b) - max(s, a) for s, e in every if s < b and e > a)
        idle.append((b - a - busy) / 1e6)
    if not idle:
        return None
    return stats.percentile(idle, 50), "ms"


def clock_error_us(records):
    """The least error the anchor's laying of host spans on the trace's
    clock provably has, over the paired spans of the traced seconds. Two
    things cannot happen: a run starts before the ``llm.decode_dispatch``
    (or ``llm.prefill``) that launched it begins, and a turn's
    ``llm.decode_readback`` ends before the run whose tokens it read does.
    The largest violation of either, 0 where there is none."""
    got = pairs(records)
    if got is None:
        return None
    spans = records["spans"]
    end = lambda s: s["t"] + s["dur_s"]  # noqa: E731
    # A turn's dispatch starts where its step span does; its read-back is
    # the last to end before the step span ends.
    readback_ends = sorted(end(s) for s in spans if s["phase"] == "llm.decode_readback")
    worst, launched = 0, set()
    for s in spans:  # in start order
        x = s["extra"]
        if "seq" not in x:
            continue
        if s["phase"] in PREFILLS and x["seq"] in got:
            worst = max(worst, _ns(records, s["t"]) - got[x["seq"]].start_ns)
        elif s["phase"] == STEP:
            new = [n for n in (x["seq"], x["next_seq"]) if n and n not in launched]
            launched.update(new)
            for n in new:  # launched by this turn's dispatch
                if n in got:
                    worst = max(worst, _ns(records, s["t"]) - got[n].start_ns)
            k = bisect.bisect_right(readback_ends, end(s)) - 1
            if k >= 0 and x["seq"] in got:
                run = got[x["seq"]]
                worst = max(worst, run.start_ns + run.dur_ns - _ns(records, readback_ends[k]))
    return worst / 1e3, "us"
