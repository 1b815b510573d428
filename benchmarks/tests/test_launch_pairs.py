"""The readers of the admitting turn's spans and of the numbered launches
(PR 37), on a hand-made timeline whose every value is known
(``data/admit_wave_spans.json`` and ``data/admit_wave_trace.json``: five
waves in the window, three of them and launches 41 to 53 in the traced
seconds), and on the spans of a commit from before them."""

import copy
import json
import os

import pytest

from benchmarks import harness, launch_pairs

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


WAVES, TRACE, BEFORE = load("admit_wave_spans.json"), load("admit_wave_trace.json"), load("serve_spans.json")
KINDS = "DDPDDPPDDDPDD"  # launches 41 to 53: decode and prefill runs as the device ran them


def records(shift_ms=0.0, spans=None):
    spans = copy.deepcopy(WAVES["spans"] if spans is None else spans)
    for s in spans:
        s["t"] += shift_ms * 1e-3
    return {"spans": spans, "window": WAVES["window"], "trace": copy.deepcopy(TRACE), "peaks": None}


@pytest.mark.parametrize("name,value,unit", [
    ("admit_wave_ms_p50.code", 35.0, "ms"),  # 25, 30.35, 35, 40.15, 50.05: the wave before the window left out
    ("admit_wave_ms_p50.batch", 35.0, "ms"),
    ("admit_wave_time_pct.batch", 100 * 0.18055 / 1.5, "%"),
    ("admit_batchable_pct.batch", 100 * (3 + 2) / 8, "%"),
    ("prefill_pad_pct.batch", 100 * (4096 - 2560) / 4096, "%"),
    # D42 | P43, another program's 0.1 ms | D44: 1.2 - 0.1; D45 | P46, P47 | D48: 0.2 + 1.6; D50 | P51 | D52: 0.5 + 0.9
    ("admit_device_idle_ms_p50.batch", 1.4, "ms"),
    ("trace_clock_error_us.batch", 0.0, "us"),
])
def test_a_reader_of_the_admitting_turn_gives_its_known_value(name, value, unit):
    read = harness.reader("layer_metrics", name)
    assert read(records()) == (pytest.approx(value, abs=1e-6), unit)
    entry = next(m for m in harness.benchmark()["per_layer"] if m["name"] == name)
    assert entry["unit"] == unit


@pytest.mark.parametrize("shift_ms", [0.0, 3.0, -3.0])
def test_the_pairing_counts_and_does_not_lean_on_the_clock(shift_ms):
    """The same pairs with the spans' clock 3 ms late and 3 ms early: the
    anchor only guesses the first run's number."""
    got = launch_pairs.pairs(records(shift_ms))
    assert sorted(got) == list(range(41, 54))
    assert "".join(got[n].kind[0].upper() for n in sorted(got)) == KINDS
    runs = [r for r in TRACE["program_runs"] if r[0].startswith("jit_paged_")]
    assert [(got[n].start_ns, got[n].dur_ns) for n in sorted(got)] == [(s, d) for _n, s, d in runs]
    idle, _ = launch_pairs.admit_device_idle_ms_p50(records(shift_ms))
    assert idle == pytest.approx(1.4)  # by count, so the same whatever the clock


@pytest.mark.parametrize("shift_ms,error_us", [
    (3.0, 2950.0),  # the second prefill of wave 8 starts 0.05 ms after its span does
    (-3.0, 2800.0),  # a step's tokens are read 0.2 ms after its run ends
])
def test_a_clock_laid_wrongly_shows_as_its_least_provable_error(shift_ms, error_us):
    assert launch_pairs.clock_error_us(records(shift_ms)) == (pytest.approx(error_us, abs=1.0), "us")


@pytest.mark.parametrize("wrong", ["prefill_on_a_decode_run", "decode_on_a_prefill_run", "step_ahead_on_a_prefill_run"])
def test_a_number_that_lands_on_the_other_program_gives_nothing(wrong):
    """Nothing rather than a wrong pairing: one span whose number falls on a
    run of the other program means the count is off."""
    spans = copy.deepcopy(WAVES["spans"])
    if wrong == "prefill_on_a_decode_run":
        next(s for s in spans if s["extra"].get("seq") == 51 and s["phase"] == "llm.prefill")["extra"]["seq"] = 49
    elif wrong == "decode_on_a_prefill_run":
        spans = [s for s in spans if s["extra"].get("seq") != 43]  # the prefill's own span is not there to agree
        next(s for s in spans if s["extra"].get("next_seq") == 44)["extra"]["seq"] = 43
    else:
        next(s for s in spans if s["extra"].get("next_seq") == 45)["extra"]["next_seq"] = 46
    rec = records(spans=spans)
    assert launch_pairs.pairs(rec) is None
    for name in ("admit_device_idle_ms_p50.batch", "trace_clock_error_us.batch"):
        assert harness.reader("layer_metrics", name)(rec) is None
    assert harness.reader("layer_metrics", "admit_wave_ms_p50.batch")(rec) is not None  # spans alone


NEW = [m["name"] for m in harness.benchmark()["per_layer"] if m["name"].startswith(("admit_", "prefill_pad_", "trace_clock_"))]


@pytest.mark.parametrize("which", ["before", "with_new_phases"])
@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_finds_nothing_on_the_spans_of_a_commit_before_it(name, which):
    """The parent records no ``llm.admit_wave`` and no ``seq``: with a trace
    or without one, every reader returns None and its metric is left out."""
    assert len(NEW) == 7
    read = harness.reader("layer_metrics", name)
    old = {"spans": BEFORE[which], "window": BEFORE["window"], "peaks": None}
    assert read({**old, "trace": None}) is None
    assert read({**old, "trace": copy.deepcopy(TRACE)}) is None
    assert read({"spans": [], "window": [0.0, 1.0], "trace": None, "peaks": None}) is None
