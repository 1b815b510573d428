"""The Kimi Linear family's files (PR 29), as new tests beside the ones that
were there (a later PR may not edit those): its output check at a size a test
run can hold (the program, driven through the engine, passes; fp8, exchanged
block tables and a stale recurrent state do not), its operation and byte
counts against numbers
reckoned by hand, and the readers of the expert layers' counters on a
recorded span file."""

import json
import os

import pytest

from benchmarks import check, harness

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "serve-batch-kimilinear"


def _tiny():
    return harness.cell_files(harness.cell(CELL), rehearsal=1)


def _published():
    return harness.config_of(harness.cell(CELL))


@pytest.mark.parametrize("seed", [1, 3000000011])
def test_program_agrees_and_the_controls_do_not(seed):
    c, mix = _tiny()
    program = check.check_one(c, mix, seed, "program")
    assert program["logits_rel_err"] < 0.02
    assert program["route_agree_pct"] > 95.0  # two picks of eight, bf16 weights: a near tie flips
    # The tables' controls are weak at this size, by the architecture: the one
    # latent layer attends without positions over random rows, so what it adds
    # is close to the rows' common part whichever rows it reads. Displaced by
    # one block, a sequence loses its newest 16 rows and gains 16 of the
    # scratch block; exchanged, the longer request reads the shorter one's
    # rows and scratch rows for the rest (most of its context at the mix's
    # real lengths: PERF.md section 2 has the chip's readings).
    # Where the rows were written is what latent_rel_err tells: the decode steps
    # after a table was wronged write beside the rows the reference expects.
    assert program["latent_rel_err"] < 0.02
    for who, times, latent in (
        ("fp8", 3, None), ("stale_state", 10, 0.1), ("swapped_tables", 1.2, 0.2), ("displaced", 1.2, 0.2),
    ):
        wrong = check.check_one(c, mix, seed, who)
        assert wrong["logits_rel_err"] > times * program["logits_rel_err"], who
        assert latent is None or wrong["latent_rel_err"] > latent, who
    with pytest.raises(SystemExit, match="unknown --who"):
        check.check_one(c, mix, seed, "int4")


def test_the_configuration_is_the_published_one_cut_as_it_says():
    c = _published()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["source_url"] == c["source"])
        differ = sorted(k for k, v in row["config"].items() if c.get(k) != v)
        assert differ == sorted(c["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
        assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    cfg = harness.family(c).model_config(c, harness.traffic_of(harness.cell(CELL)))
    assert cfg.kda_layers == tuple(c["layers_held"]["kda_layers"]) == (1, 2, 3, 5, 6, 7, 9)
    assert cfg.mla_layers == tuple(c["layers_held"]["full_attn_layers"]) == (4, 8)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset) == (256, 64, 0)
    assert (cfg.d_model, cfg.kda_heads, cfg.kda_head_dim, cfg.latent_dim) == (2304, 32, 128, 576)
    assert cfg.state_slots == 16 and cfg.max_seq == 2048
    # the floors a cut keeps: a whole period and four more layers, 8 experts, 1/8 of the vocabulary
    assert c["num_hidden_layers"] >= 1 + 4 + 4 and c["num_experts"] >= 8
    assert c["vocab_size"] * 8 >= c["published"]["vocab_size"]


def test_weight_cache_and_state_bytes_by_hand():
    """ISSUE 29's arithmetic, in this repo's bytes (bf16 2 B, router and state float32)."""
    c, fam = _published(), harness.family(_published())
    D, V = 2304, 40960
    kda = 3 * D * 4096 + 4096 * D + 2 * (D * 128 + 128 * 4096) + D * 32  # 39.4 M in matrices
    kda_small = 4 * 12288 + 128  # convolutions, the output norm
    kda_f32 = (4096 + 32) * 4  # dt_bias and A_log, float32
    mla = D * 6144 + D * 576 + 512 * 8192 + 4096 * D  # 29.1 M
    expert = 3 * D * 1024  # 7.08 M
    router = (D * 256 + 256) * 4  # float32, with its bias
    non_expert = 2 * (
        7 * (kda + kda_small) + 2 * (mla + 512) + 3 * D * 9216 + 8 * expert + 2 * D * 9 + D + D * V
    ) + 8 * router + 7 * kda_f32
    assert fam.non_expert_weight_bytes(c) == non_expert == 1_118_079_104
    assert fam.weight_bytes(c) == non_expert + 8 * 64 * expert * 2  # + 7.25 GB of held experts
    assert fam.kv_bytes_per_token(c) == 2 * 576 * 2  # two MLA layers, one latent row each
    assert fam.state_bytes_per_slot(c) == 7 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    # the parameters the program draws: the counts above and the embedding table
    import jax

    cfg = fam.model_config(c, harness.traffic_of(harness.cell(CELL)))
    shapes = jax.eval_shape(lambda k: fam.init_params(k, cfg), jax.random.key(0))
    drawn = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert drawn == fam.weight_bytes(c) + V * D * 2 == 8_554_580_096


def test_decode_step_and_prefill_counts_by_hand():
    c, fam = _published(), harness.family(_published())
    assert fam.experts_touched(c, 1) == pytest.approx(2.0)  # 8 picks, a quarter of them here
    assert fam.experts_touched(c, 9.5) == pytest.approx(64 * (1 - (1 - 8 / 256) ** 9.5))
    assert fam.experts_touched(c, 1e4) == pytest.approx(64.0)
    batch, context = 10, 12000
    ops, nbytes = fam.decode_step(c, batch, context)
    touched = 64 * (1 - (31 / 32) ** 10)
    assert nbytes == pytest.approx(
        1_118_079_104 + 8 * touched * 3 * 2304 * 1024 * 2  # weights once, touched experts
        + 2 * batch * 15_196_160 + 2304 * (context + batch)  # state in and out, latent rows
    )
    # the reader of the roofline share hands over what the program counted
    assert fam.decode_step(c, batch, context, touched=8 * touched) == (ops, nbytes)
    assert fam.decode_step(c, batch, context, touched=0)[1] == pytest.approx(
        nbytes - 8 * touched * 3 * 2304 * 1024 * 2)
    D = 2304
    per_token = 2 * (
        7 * (3 * D * 4096 + 4096 * D + 2 * (D * 128 + 128 * 4096) + D * 32)
        + 2 * (D * 6144 + D * 576 + 512 * 8192 + 4096 * D) + 3 * D * 9216
        + 8 * (D * 256 + 3 * D * 1024 + 2 * 3 * D * 1024)  # router, shared, two picks here
    )
    recurrence = 7 * 32 * 7 * 128 * 128
    assert ops == pytest.approx(
        batch * (per_token + 2 * D * 40960 + recurrence) + 2 * 2 * 32 * (2 * 512 + 64) * context
    )
    # memory-bound by a wide margin: the roofline reader's share is of bytes
    assert nbytes / 819e9 > 20 * ops / 197e12
    T = 1024
    ops, nbytes = fam.prefill(c, T)
    assert ops == pytest.approx(
        T * (per_token + recurrence) + 2 * D * 40960 + 2 * 2 * 32 * (192 + 128) * T * (T + 1) / 2
    )
    assert nbytes == fam.weight_bytes(c) + 2304 * T + 15_196_160
    assert 1.2e12 < ops < 1.25e12  # PERF.md quotes 1.22 TFLOP for the 1,024 bucket


SPANS = json.load(open(os.path.join(HERE, "data", "kimi_linear_spans.json")))


def _records(which, trace=None):
    return {"spans": SPANS[which], "window": SPANS["window"], "trace": trace, "peaks": None}


@pytest.mark.parametrize("name,value", [
    ("moe_experts_touched_pct", 100.0 * (140 + 116) / 1024),  # the two steps inside the window
    ("moe_picks_here_pct", 100.0 * (150 + 154) / (640 + 576)),
])
def test_an_expert_reader_reads_its_fields_and_nothing_before_they_existed(name, value):
    read = harness.reader("layer_metrics", name)
    assert read(_records("with_expert_fields")) == (pytest.approx(value), "%")
    assert read(_records("before_the_fields")) is None
    assert read({"spans": [], "window": SPANS["window"], "trace": None, "peaks": None}) is None
    # a traced run reads the traced seconds only
    traced = {"t0_wall": 101.5, "window_s": 1.0}
    first = SPANS["with_expert_fields"][1]["extra"]
    part, whole = ("experts_touched", "experts_held") if "touched" in name else ("picks_here", "picks")
    assert read(_records("with_expert_fields", traced)) == (
        pytest.approx(100.0 * first[part] / first[whole]), "%")


def test_prefill_share_of_a_batch_cell_is_device_time_of_prefill_over_both_programs_by_name():
    read = harness.reader("layer_metrics", "prefill_time_pct.batch")
    assert read(_records("with_expert_fields")) is None  # no trace, nothing to read
    ms = 1_000_000
    reduced = {"offset_ns": 0, "program_runs": [
        ["jit_paged_prefill(123)", 1000 * ms, 40 * ms],
        ["jit_paged_decode(77)", 1050 * ms, 10 * ms],
        ["jit_paged_decode(77)", 1065 * ms, 10 * ms],
        ["jit_paged_prefill(456)", 1080 * ms, 20 * ms],  # another bucket
        ["jit_convert_element_type(9)", 1105 * ms, 20_000],
    ]}
    # a decode span that the drifted clock lays just after its own program's
    # start is paired with the prefill that follows by the span-based reader
    spans = [
        {"phase": "llm.prefill", "t": 0.9995, "dur_s": 0.045, "extra": {}},
        {"phase": "llm.decode_step", "t": 1.0651, "dur_s": 0.014, "extra": {"batch": 9}},
    ]
    rec = {"spans": spans, "window": [0.0, 2.0], "trace": reduced, "peaks": None}
    assert read(rec) == (pytest.approx(100.0 * 60 / 80), "%")
    # (it takes prefill(456) for the decode program and so never sees the decode runs)
    assert harness.reader("layer_metrics", "prefill_time_pct")(rec) == (pytest.approx(100.0 * 40 / 60), "%")
    assert read({**rec, "trace": {"offset_ns": 0, "program_runs": [["jit__unknown(1)", 0, 5 * ms]]}}) is None


def test_decode_roofline_share_by_program_name_and_by_the_experts_the_program_counted():
    from benchmarks import flops_bytes

    read = harness.reader("layer_metrics", "pg_decode_roofline_pct.kimilinear")
    c, fam = _published(), harness.family(_published())
    peaks = harness.load_json(harness.HERE, "peaks.json")["TPU v5 lite"]
    ms = 1_000_000
    reduced = {"offset_ns": 0, "t0_wall": 101.5, "window_s": 1.0, "program_runs": [
        ["jit_paged_prefill(123)", 1000 * ms, 60 * ms],  # no part of the decode program's time
        ["jit_paged_decode(77)", 1065 * ms, 8 * ms],
        ["jit_paged_decode(77)", 1080 * ms, 10 * ms],
    ]}
    requests = [{"prompt_tokens": 1000, "tokens": [101.6, 101.7, 103.0]}]
    rec = {**_records("with_expert_fields", reduced), "peaks": peaks, "config": c, "requests": requests}
    step = SPANS["with_expert_fields"][1]["extra"]  # the one step inside the traced second
    ops, nbytes = fam.decode_step(c, step["batch"], 1000 + 1001, touched=step["experts_touched"])
    assert read(rec) == (pytest.approx(flops_bytes.roofline_pct(ops, nbytes, 0.009, peaks)[0]), "%")
    assert read({**rec, "spans": SPANS["before_the_fields"]}) is None
    assert read({**rec, "peaks": None}) is None and read({**rec, "trace": None}) is None
