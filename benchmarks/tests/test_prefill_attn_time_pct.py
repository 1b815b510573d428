"""The reader of ``prefill_attn_time_pct``: the prefill kernel's operations
over the prefill programs' runs, both from the reduced trace."""

import pytest

from benchmarks import harness

TRACE = {
    "t0_wall": 100.0, "window_s": 4.0,
    "ops": [
        ["paged_decode_attention_full.2", 0.9], ["paged_prefill_attention_full.2", 0.11],
        ["paged_prefill_attention_window.5", 0.02], ["paged_prefill_attention.7", 0.07], ["fusion.1", 0.5],
    ],
    "program_runs": [
        ["jit_paged_prefill", 0, 70_000_000], ["jit_paged_decode", 1, 9_000_000],
        ["jit_paged_prefill.1", 2, 30_000_000],
    ],
}


def test_the_share_of_the_prefill_programs_time_inside_the_prefill_kernel():
    read = harness.reader("layer_metrics", "prefill_attn_time_pct")
    assert read({"trace": TRACE}) == (pytest.approx(100.0 * 0.2 / 0.1), "%")
    # nothing to read: no trace, no run of the program, no such operation (the fold, or a commit before the kernel)
    assert read({"trace": None}) is None
    assert read({"trace": {**TRACE, "program_runs": TRACE["program_runs"][1:2]}}) is None
    assert read({"trace": {**TRACE, "ops": [op for op in TRACE["ops"] if "prefill" not in op[0]]}}) is None


def test_the_metric_is_listed_with_its_file_and_moves_the_chunked_cells_metric():
    entry = next(m for m in harness.benchmark()["per_layer"] if m["name"] == "prefill_attn_time_pct")
    assert entry["moves"] == "out_tok_s" and entry["better"] == "lower" and entry["source"] == "device_trace"
    assert "serve-longdoc-mimov25" in entry["workloads"]
