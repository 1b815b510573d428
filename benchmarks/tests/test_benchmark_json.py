"""BENCHMARK.json against the files it names: adding a cell needs new files
and new entries only, because everything is found by name."""

import os
import re

import pytest

from benchmarks import harness

B = harness.benchmark()
CELLS = [w["name"] for w in B["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_its_files_by_name(name):
    cell = harness.cell(name)
    config = harness.config_of(cell)
    traffic = harness.traffic_of(cell)
    assert config["name"] == cell["config"]
    assert traffic["kind"] in ("open-loop", "closed-loop", "train")
    for key in ("source", "reduced", "assumed", "deployment", "dtype"):
        assert key in config, key
    entry = next(c for c in B["configs"] if c["name"] == cell["config"])
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    assert os.path.exists(os.path.join(harness.HERE, "limits", cell["config"] + ".json"))


@pytest.mark.parametrize("name", CELLS)
def test_cell_reports_setup_one_more_end_to_end_metric_and_a_layer_metric(name):
    e2e = [m["name"] for m in harness.metrics_of(name, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_of(name, "per_layer")


@pytest.mark.parametrize("group,directory", [("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")])
def test_every_metric_has_a_reader_of_its_own(group, directory):
    for m in B[group]:
        assert callable(harness.reader(directory, m["name"])), m["name"]
    on_disk = {f[:-3] for f in os.listdir(os.path.join(harness.HERE, directory)) if f.endswith(".py")}
    assert on_disk == {m["name"] for m in B[group]}


def test_every_layer_metric_moves_a_metric_its_cells_report():
    for m in B["per_layer"]:
        for cell in m["workloads"]:
            reported = [e["name"] for e in harness.metrics_of(cell, "end_to_end")]
            assert m["moves"] in reported, (m["name"], cell)
            assert m["moves"] != "setup_s"


def test_the_contract_limits_on_the_file():
    assert B["paths"] == ["benchmarks"] and B["command"] == ["python3", "benchmarks/run.py"]
    assert sum(w["chips"] == 4 for w in B["workloads"]) == 1
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in B[group]:
            assert name.match(entry["name"]), entry["name"]
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    # the window fits the driver's budget with the full 24 cells
    s = B["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) < 64 * 1024


def test_no_general_file_knows_a_name_of_the_benchmark():
    """The harness holds no table of names and no branch on one: a name from
    BENCHMARK.json appears in no file that is not that name's own."""
    names = {e["name"] for g in ("configs", "workloads", "end_to_end", "per_layer") for e in B[g]}
    names |= {w["traffic"] for w in B["workloads"]}
    general = ["run.py", "harness.py", "serve_driver.py", "serve_replica.py",
               "train_driver.py", "traffic_gen.py", "stats.py", "trace_reduce.py",
               "model_build.py"]
    for fname in general:
        with open(os.path.join(harness.HERE, fname)) as f:
            text = f.read()
        found = [n for n in names if n != "setup_s" and re.search(r"[\"']" + re.escape(n) + r"[\"']", text)]
        assert not found, (fname, found)


def test_a_reader_that_finds_nothing_leaves_its_metric_out(monkeypatch):
    records = {"trace": None, "spans": [], "window": [0.0, 1.0], "requests": [
        {"due": 0.0, "sent": 0.0, "tokens": [0.1, 0.2], "prompt_tokens": 8, "max_tokens": 2}],
        "compile_events": [[0.5, "backend_compile"], [0.5, "cache_hit"], [0.7, "backend_compile"]],
        "peaks": None, "config": {}, "traffic": {}}
    got = harness.read_metrics(CELLS[0], True, records)
    assert "device_idle_pct.code" not in got and "pg_prefill_dev_ms_p50" not in got
    assert got["window_compiles.code"] == {"value": 1.0, "unit": "programs"}
    assert got["client_ttft_p50_ms"]["value"] == pytest.approx(100.0)
