import json
import os

import pytest

from benchmarks import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        return json.load(f)


def test_busy_idle_and_window(trace):
    r = tr.reduce(trace)
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(200e-6)
    # idle: 100..140 between the programs, 160..165 inside the second
    assert r["busy_s"] == pytest.approx(155e-6)
    assert r["programs"] == {"jit_step(123)": [pytest.approx(100e-6), pytest.approx(60e-6)]}


def test_gaps_are_named_after_the_host_span_over_them(trace):
    t0 = 1_000_000
    spans = [
        ("train.step", t0 - 10_000, t0 + 300_000),  # covers everything
        ("train.d2h_report", t0 + 110_000, t0 + 130_000),  # the shortest over the gap's middle
    ]
    gaps = dict(tr.reduce(trace, spans)["idle_gaps"])
    assert gaps == {
        "train.d2h_report": pytest.approx(40e-6),
        "between_ops_of_a_running_program": pytest.approx(5e-6),
    }
    assert dict(tr.reduce(trace)["idle_gaps"])["host_outside_any_span"] == pytest.approx(40e-6)


def test_self_times_take_nested_children_out(trace):
    ops = dict(tr.reduce(trace)["ops"])
    assert ops["fusion.1"] == pytest.approx(65e-6)  # 30 + 35
    assert ops["fusion.2"] == pytest.approx(40e-6)
    assert ops["while.1"] == pytest.approx(20e-6)  # 100 less its 80 of children
    assert sum(ops.values()) == pytest.approx(155e-6)  # same as busy: one stream


def test_exposed_collective_time(trace):
    r = tr.reduce(trace)
    # all-gather.1 (10 us) runs alone; all-reduce-start.1 (20 us, a line of its
    # own) lies wholly under fusion.2
    assert r["collective_s"] == pytest.approx(30e-6)
    assert r["collective_exposed_s"] == pytest.approx(10e-6)


def test_clock_anchor_gives_the_offset(trace):
    assert tr.clock_offset_ns(trace, 5_000_900_000) == 5_000_000_000
    assert tr.clock_offset_ns({"planes": []}, 1) is None


def test_breakdown_is_capped(trace):
    b = tr.breakdown(tr.reduce(trace), top=2)
    assert len(b["device_ops"]) == 2 and b["device_ops"][0][0] == "fusion.1"


def test_a_trace_with_no_device_op_is_refused():
    with pytest.raises(ValueError):
        tr.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})


def test_layer_readers_on_the_reduced_trace(trace):
    from benchmarks import harness

    records = {"trace": tr.reduce(trace)}
    assert harness.reader("layer_metrics", "device_idle_pct.train")(records) == (
        pytest.approx(22.5), "%")
    assert harness.reader("layer_metrics", "collective_exposed_pct")(records) == (
        pytest.approx(5.0), "%")
    assert harness.reader("layer_metrics", "device_idle_pct.code")({"trace": None}) is None


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "recorded_decode_steps.json")) as f:
        return json.load(f)


def test_recorded_trace_of_two_decode_steps(recorded):
    """A slice of a real v5e trace (two runs of the paged decode program)."""
    r = tr.reduce(recorded)
    (runs,) = r["programs"].values()
    assert runs == [pytest.approx(0.037983201), pytest.approx(0.037984877)]
    assert r["window_s"] == pytest.approx(0.08020846)
    assert r["busy_s"] == pytest.approx(0.075967494)
    # one stream: the ops' own times add up to the busy time
    assert sum(s for _n, s in r["ops"]) == pytest.approx(r["busy_s"], rel=1e-6)
    assert r["ops"][0][0] == "copy.41" and r["collective_s"] == 0.0
    assert dict(r["idle_gaps"])["host_outside_any_span"] == pytest.approx(0.004240926)


def test_programs_are_told_apart_by_the_span_that_starts_them(recorded):
    r = tr.reduce(recorded)
    first, second = (run[1] for run in r["program_runs"])
    r["offset_ns"] = 1_000_000_000_000  # wall = trace + 1000 s
    at = lambda ns: (ns + r["offset_ns"]) / 1e9  # noqa: E731
    spans = [
        {"phase": "llm.decode_step", "t": at(first - 300_000), "dur_s": 0.042},
        {"phase": "llm.prefill", "t": at(second + 60_000_000), "dur_s": 0.001},  # nothing follows
    ]
    assert tr.runs_of_phase(r, spans, "llm.decode_step") == [
        pytest.approx(0.037983201), pytest.approx(0.037984877)]
    assert tr.runs_of_phase(r, spans, "llm.prefill") == []
    assert tr.runs_of_phase({**r, "offset_ns": None}, spans, "llm.decode_step") == []


def test_hlo_lines_are_cut_to_the_instruction_name():
    assert tr.short_name("%copy.41 = bf16[16,8]{1,0} copy(bf16[16,8]{0,1} %bitcast.190)") == "copy.41"
    assert tr.short_name("jit__unknown(123)") == "jit__unknown(123)"
