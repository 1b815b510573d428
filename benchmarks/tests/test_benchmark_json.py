"""BENCHMARK.json against the files it names: adding a cell needs new files
and new entries only, because everything is found by name."""

import dataclasses
import json
import os
import re
import shutil

import pytest

from benchmarks import check, harness, model_build

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


GENERAL = ["run.py", "harness.py", "serve_driver.py", "serve_replica.py",
           "train_driver.py", "traffic_gen.py", "stats.py", "trace_reduce.py",
           "model_build.py", "check.py", "flops_bytes.py", "aot_rehearsal.py"]
FAMILIES = sorted(f[:-3] for f in os.listdir(os.path.join(harness.HERE, "families")) if f.endswith(".py"))


def test_no_general_file_knows_a_name_of_the_benchmark():
    """The harness holds no table of names and no branch on one: a name from
    BENCHMARK.json, or of a file under families/, appears in no file that is
    not that name's own."""
    names = {e["name"] for g in ("configs", "workloads", "end_to_end", "per_layer") for e in B[g]}
    names |= {w["traffic"] for w in B["workloads"]} | set(FAMILIES)
    for fname in GENERAL:
        with open(os.path.join(harness.HERE, fname)) as f:
            text = f.read()
        found = [n for n in names if n != "setup_s" and re.search(r"[\"']" + re.escape(n) + r"[\"']", text)]
        found += [n for n in ("llama", "gpt2") if n in text]  # PR 28: not even in a comment
        assert not found, (fname, found)


TRAINS = ("init_params", "loss_fn", "forward", "param_logical_specs", "train_flops_per_token", "num_params")
SERVES = ("init_params", "decode_step", "prefill", "weight_bytes", "kv_bytes_per_token")
NEEDS = {"train": TRAINS, "open-loop": SERVES, "closed-loop": SERVES}


@pytest.mark.parametrize("name", CELLS)
def test_the_family_of_a_cell_resolves_and_has_what_its_kind_needs(name):
    cell = harness.cell(name)
    config, traffic = harness.config_of(cell), harness.traffic_of(cell)
    fam = harness.family(config)
    assert fam.__file__ == os.path.join(harness.HERE, "families", config["family"] + ".py")
    for f in ("model_config", "check", "shrink") + NEEDS[traffic["kind"]]:
        assert callable(getattr(fam, f, None)), (config["family"], f)
    assert os.path.exists(os.path.join(harness.HERE, "reference", config["family"] + "_ref.py"))
    tiny = fam.shrink(config)
    assert tiny is not config and tiny != config


def test_a_family_without_a_file_fails_with_the_path():
    with pytest.raises(SystemExit, match=r"benchmarks/families/retnet\.py"):
        harness.family({"family": "retnet"})


TOY_FAMILY = '''
def model_config(c, traffic):
    return ("toy", c["width"], traffic["engine"]["max_seq"])

def shrink(c):
    return {**c, "width": 8}

def check(c, traffic, seed, who, devices=None):
    return {"toy_err": 0.0 if who == "program" else 1.0}
'''


def test_a_family_added_as_files_alone_is_found_by_every_general_file(tmp_path, monkeypatch):
    """What a model_config PR may bring: families/<x>.py, a configuration, its
    limits, entries in BENCHMARK.json. Written into a copy of the tree, they
    are reached through harness.family, model_build.llm_config,
    check.check_one and shrink_for_rehearsal, with every general file as it is."""
    real, here = harness.HERE, tmp_path / "benchmarks"
    shutil.copytree(real, here, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    mix_name = next(w["traffic"] for w in B["workloads"] if harness.traffic_of(w)["kind"] != "train")
    (here / "families" / "toy.py").write_text(TOY_FAMILY)
    toy = {"name": "toy-1b", "family": "toy", "width": 4096, "source": "a paper", "reduced": []}
    (here / "configs" / "toy-1b.json").write_text(json.dumps(toy))
    (here / "limits" / "toy-1b.json").write_text(json.dumps({"limits": {"toy_err": 0.5}}))
    bench = json.loads(json.dumps(B))
    bench["configs"].append({"name": "toy-1b", "file": "benchmarks/configs/toy-1b.json"})
    bench["workloads"].append({"name": "serve-toy", "config": "toy-1b", "traffic": mix_name, "chips": 1})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "HERE", str(here))
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    harness.benchmark.cache_clear()
    try:
        cell = harness.cell("serve-toy")
        config, mix = harness.cell_files(cell, rehearsal=0)
        assert harness.family(config).__file__ == str(here / "families" / "toy.py")
        llm = model_build.llm_config(config, mix, seed=3)
        assert llm.model_id == "toy-1b" and llm.model_config == ("toy", 4096, mix["engine"]["max_seq"])
        assert check.check_one(config, mix, 7, "program") == {"toy_err": 0.0}
        assert check.check_one(config, mix, 7, "fp8") == {"toy_err": 1.0}
        tiny, tiny_mix = harness.cell_files(cell, rehearsal=1)
        assert tiny["width"] == 8 and config["width"] == 4096
        assert tiny_mix["engine"]["max_seq"] < mix["engine"]["max_seq"]  # by kind, as for any family
        for fname in GENERAL:
            with open(here / fname, "rb") as new, open(os.path.join(real, fname), "rb") as old:
                assert new.read() == old.read(), fname
    finally:
        harness.benchmark.cache_clear()


@pytest.mark.parametrize("mix_name", ["code-poisson", "batch-backlog"])
def test_the_engine_settings_of_todays_mixes_are_what_they_were(mix_name):
    """llm_config passes whatever the mix's engine names; these two name the
    five settings that were passed one by one before, so their LLMConfigs are
    equal field for field."""
    from ray_tpu.llm.config import LLMConfig

    cell = next(w for w in B["workloads"] if w["traffic"] == mix_name)
    c, mix = harness.config_of(cell), harness.traffic_of(cell)
    e = mix["engine"]
    was = LLMConfig(
        model_id=c["name"],
        model_config=harness.family(c).model_config(c, mix),
        max_slots=e["max_slots"],
        max_seq=e["max_seq"],
        prefill_buckets=tuple(e["prefill_buckets"]),
        kv_block_size=e["kv_block_size"],
        num_kv_blocks=e["num_kv_blocks"],
        placement={"num_tpus": 1, "num_cpus": 1},
        seed=11,
    )
    got = model_build.llm_config(c, mix, 11)
    assert {k for k in e if not k.endswith("_why")} == {
        "max_slots", "max_seq", "prefill_buckets", "kv_block_size", "num_kv_blocks"}
    for f in dataclasses.fields(LLMConfig):
        assert getattr(got, f.name) == getattr(was, f.name), f.name
    assert isinstance(got.prefill_buckets, tuple)


def test_a_mix_turns_on_any_setting_the_engine_has_and_no_other():
    cell = next(w for w in B["workloads"] if harness.traffic_of(w)["kind"] != "train")
    c, mix = harness.config_of(cell), harness.traffic_of(cell)
    more = {**mix, "engine": {**mix["engine"], "prefill_chunk_tokens": 256,
                              "prefill_chunk_tokens_why": "prose is not passed on"}}
    assert model_build.llm_config(c, more, 1).prefill_chunk_tokens == 256
    for bad in ("prefil_chunk_tokens", "seed", "model_config"):
        with pytest.raises(SystemExit, match=bad):
            model_build.llm_config(c, {**mix, "engine": {**mix["engine"], bad: 1}}, 1)


def test_a_reader_that_finds_nothing_leaves_its_metric_out(monkeypatch):
    records = {"trace": None, "spans": [], "window": [0.0, 1.0], "requests": [
        {"due": 0.0, "sent": 0.0, "tokens": [0.1, 0.2], "prompt_tokens": 8, "max_tokens": 2}],
        "compile_events": [[0.5, "backend_compile"], [0.5, "cache_hit"], [0.7, "backend_compile"]],
        "peaks": None, "config": {}, "traffic": {}}
    got = harness.read_metrics(CELLS[0], True, records)
    assert "device_idle_pct.code" not in got and "pg_prefill_dev_ms_p50" not in got
    assert got["window_compiles.code"] == {"value": 1.0, "unit": "programs"}
    assert got["client_ttft_p50_ms"]["value"] == pytest.approx(100.0)
