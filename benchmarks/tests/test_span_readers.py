"""The per-layer readers of the spans and names that PR 26 added to the
program: each gives its value where its phase is recorded and None on the
spans and traces of a commit from before."""

import copy
import json
import os

import pytest

from benchmarks import harness, trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


SPANS = load("serve_spans.json")


def records(which):
    return {"spans": SPANS[which], "window": SPANS["window"], "trace": None, "peaks": None}


@pytest.mark.parametrize("name,value", [
    ("decode_host_ms_p50.code", 2.4),  # steps in the window: 0.8 + 1.0 and 1.2 + 1.8
    ("decode_host_ms_p50.batch", 2.4),
    ("pump_gap_ms_p50.code", 4.0),
    ("pump_gap_ms_p50.batch", 4.0),
    ("engine_queue_ms_p50", 30.0),
    ("prefill_span_ms_p50", 48.0),
    ("serve_hop_in_ms_p50", 4.0),
    ("replica_first_chunk_ms_p50", 90.0),
])
def test_a_span_reader_reads_its_phase_and_nothing_before_it_existed(name, value):
    read = harness.reader("layer_metrics", name)
    assert read(records("with_new_phases")) == (pytest.approx(value), "ms")
    assert read(records("before")) is None


def test_the_parts_of_a_step_add_up_to_the_step_reader():
    rec = records("with_new_phases")
    step, _ = harness.reader("layer_metrics", "engine_step_ms_p50.code")(rec)
    host, _ = harness.reader("layer_metrics", "decode_host_ms_p50.code")(rec)
    readback = [s["dur_s"] * 1e3 for s in rec["spans"]
                if s["phase"] == "llm.decode_readback" and s["t"] >= rec["window"][0]]
    assert host + sum(readback) / len(readback) == pytest.approx(step)


def train_records(rename):
    trace = copy.deepcopy(load("small_trace.json"))
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for e in line["events"]:
                e[0] = rename.get(e[0], e[0])
    return {
        "trace": tr.reduce(trace),
        "config": harness.load_json(harness.HERE, "configs", "gpt2-xl.json"),
        "traffic": harness.load_json(harness.HERE, "traffic", "pretrain-s1024.json"),
        "peaks": harness.peaks_for("TPU v5 lite"),
    }


def test_flash_roofline_reads_the_named_kernels():
    read = harness.reader("layer_metrics", "flash_roofline_pct")
    rec = train_records({"fusion.1": "flash_fwd.1", "fusion.2": "flash_bwd.2"})
    # One device's shard under fsdp=4: 2 sequences x 25 heads x 1024 x 64, bf16.
    fwd_ops = 2 * 25 * 2 * 2 * 64 * 1024 * 1025 / 2
    tensor, row = 2 * 25 * 1024 * 64 * 2, 2 * 25 * 1024 * 4
    least = 48 * (
        max(fwd_ops / 197e12, (4 * tensor + row) / 819e9)
        + max(2 * fwd_ops / 197e12, (7 * tensor + 2 * row) / 819e9)
    )
    # flash ops take 65 + 40 of the 155 us the device is busy; a step is the
    # median run of the one program, 80 us
    flash_step = 105 / 155 * 80e-6
    value, unit = read(rec)
    assert unit == "%" and value == pytest.approx(100 * least / flash_step)
    assert least == pytest.approx(4.91e-3, rel=1e-2)  # 102 us a layer: compute-bound


def test_flash_roofline_reads_nothing_where_the_kernels_have_no_name():
    read = harness.reader("layer_metrics", "flash_roofline_pct")
    assert read(train_records({"fusion.1": "shard_map.267", "fusion.2": "shard_map.266"})) is None
    assert read({"trace": None, "peaks": None}) is None
    assert read({**train_records({"fusion.1": "flash_fwd.1"}), "peaks": None}) is None
