"""The ``solar_open2`` family's files (PR 43): its output check at a size a test
run can hold (the program, driven through the engine with its prompts in
chunks that carry the state, passes; the reference in fp8, with ``beta`` a
plain sigmoid, without the gate, rotated, from a zero state or a zero tail at
the second chunk, and a wronged block table do not), the configuration against
the catalog, its operation and byte counts against ``init_params``' shapes to
the byte and against ISSUE 43's arithmetic, the two readers this cell brings
and the two roofline readers it joins, on synthetic records."""

import json
import os

import pytest

from benchmarks import check, flops_bytes, harness

CELL = "serve-longdoc-solaropen2"


def _tiny():
    """The rehearsal's sizes, with chunks short enough that its prompts
    (124 tokens and 32 + 9) go through them."""
    c, mix = harness.cell_files(harness.cell(CELL), rehearsal=1)
    mix["engine"].update(prefill_chunk_tokens=32, max_slots=4)
    return c, mix


def _published():
    return harness.config_of(harness.cell(CELL))


@pytest.fixture(scope="module", params=[3, 3000000015])
def program(request):
    c, mix = _tiny()
    return request.param, check.check_one(c, mix, request.param, "program")


def test_the_program_agrees_with_the_reference(program):
    """bf16 weights and activations at the tiny widths, through the engine with
    its churn and its chunks: logits, keys and values, state and tail."""
    _seed, got = program
    assert got["logits_rel_err"] < 0.06 and got["kv_rel_err"] < 0.01 and got["state_rel_err"] < 0.05
    assert got["route_agree_pct"] > 90


@pytest.mark.parametrize("who, number, times", [
    ("fp8", "logits_rel_err", 3),
    ("beta_unit", "state_rel_err", 5),
    ("ungated", "logits_rel_err", 2),
    ("rotated", "kv_rel_err", 20),
    ("stale_state", "state_rel_err", 5),
    ("lost_tail", "state_rel_err", 5),
    ("displaced", "kv_rel_err", 20),
])
def test_every_control_is_outside_a_number_the_program_is_inside(program, who, number, times):
    seed, right = program
    c, mix = _tiny()
    wrong = check.check_one(c, mix, seed, who)
    assert set(wrong) >= {"logits_rel_err", "kv_rel_err", "state_rel_err"}
    assert wrong[number] > times * right[number], (who, wrong, right)


def test_an_unknown_control_is_refused():
    c, mix = _tiny()
    with pytest.raises(SystemExit, match="unknown --who"):
        check.check_one(c, mix, 1, "int4")


def test_the_configuration_is_the_published_one_cut_as_it_says():
    c = _published()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["source_url"] == c["source"])
        differ = sorted(k for k, v in row["config"].items() if c.get(k) != v)
        assert differ == sorted(c["reduced"]) == ["gqa_layers", "n_routed_experts", "num_hidden_layers", "vocab_size"]
        assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    for key in ("kda_gate_rank", "kda_decay", "kda_normalisation", "kda_beta", "kda_heads", "gqa_gate",
                "gqa_norm", "router", "intermediate_size"):
        assert key in c["assumed"], key
    assert "8 chips share each layer" in c["deployment"] and "12 pipeline stages" in c["deployment"]
    mix = harness.traffic_of(harness.cell(CELL))
    cfg = harness.family(c).model_config(c, mix)
    assert cfg.layer_kinds == ("gqa", "kda", "kda", "kda") and cfg.kda_neg_eigval
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset, cfg.experts_per_token) == (320, 40, 0, 8)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim) == (4096, 64, 8, 128)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.conv_kernel, cfg.kda_gate_rank) == (64, 128, 4, 128)
    assert (cfg.moe_d_ff, cfg.n_shared_experts, cfg.routed_scaling, cfg.renormalize) == (1280, 1, 1.0, True)
    assert (cfg.max_seq, cfg.state_slots, cfg.vocab_size, cfg.silent_ids) == (18432, 32, 24576, (257,))
    # the floors: a whole period, eight experts a layer, an eighth of the vocabulary
    assert c["num_hidden_layers"] >= 4 and c["n_routed_experts"] >= 8
    assert c["vocab_size"] * 8 >= c["published"]["vocab_size"]
    # the mix is the issue's, letter for letter
    assert mix["clients"] == mix["engine"]["max_slots"] == 32 and mix["kind"] == "closed-loop"
    assert mix["prompt_tokens"] == [6144, 6144, 8192, 8192, 8192, 8192, 10240, 10240, 10240, 12288, 12288,
                                    12288, 14336, 14336, 16384, 16384]
    assert mix["output_tokens"] == [384, 400, 416, 432, 448, 464, 480, 512, 512, 528, 544, 560, 576, 592, 608, 640]
    assert sum(mix["prompt_tokens"]) / 16 == 10880 and sum(mix["output_tokens"]) / 16 == 506
    assert all(p % 2048 == 0 for p in mix["prompt_tokens"])  # no tail bucket compiles inside the window
    e = mix["engine"]
    assert (e["max_seq"], e["kv_block_size"], e["num_kv_blocks"], e["prefill_chunk_tokens"]) == (18432, 16, 36865, 2048)
    assert e["num_kv_blocks"] == 32 * (18432 // 16) + 1
    assert e["prefill_buckets"] == [32, 64, 128, 256, 512, 1024, 2048, 8192, 16384]
    cell = harness.cell(CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and len(c["source"]) <= 200


def test_weight_cache_and_state_bytes_by_hand_and_by_the_shapes_the_program_draws():
    """ISSUE 43's arithmetic, in this repo's bytes (bf16 2 B; the router, its
    bias, A_log and dt_bias float32)."""
    c, fam = _published(), harness.family(_published())
    D, V = 4096, 24576
    kda = 3 * D * 8192 + 8192 * D + 2 * (D * 128 + 128 * 8192) + D * 64
    gqa = 3 * D * 8192 + 2 * D * 1024
    assert round(kda / 1e5) == 1376 and round(gqa / 1e5) == 1091  # 137.6 M, 109.1 M
    expert = 3 * D * 1280
    assert expert == 15_728_640
    small = 4 * 3 * 8192 + 128  # a KDA layer's convolutions and output norm
    f32 = (8192 + 64) * 4  # its dt_bias and A_log
    router = (D * 320 + 320) * 4
    non_expert = 2 * (3 * (kda + small) + gqa + 4 * (expert + 2 * D) + D + D * V) + 3 * f32 + 4 * router
    assert fam.non_expert_weight_bytes(c) == non_expert
    assert fam.weight_bytes(c) == non_expert + 4 * 40 * expert * 2
    assert fam.kv_bytes_per_token(c) == 2 * 8 * 128 * 2 == 4096
    assert fam.state_bytes_per_slot(c) == 3 * (64 * 128 * 128 * 4 + 3 * 24576 * 2) == 13_025_280
    import jax

    from ray_tpu.models import paged

    mix = harness.traffic_of(harness.cell(CELL))
    cfg = fam.model_config(c, mix)
    shapes = jax.eval_shape(lambda k: fam.init_params(k, cfg), jax.random.key(0))
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))  # noqa: E731
    assert nbytes(shapes) == fam.weight_bytes(c) + V * D * 2 == 6_627_244_544  # 6.63 GB
    assert round(sum(x.size for x in jax.tree.leaves(shapes)) / 1e6) == 3308  # "3,308 M parameters"
    e = mix["engine"]
    for pool in (
        jax.eval_shape(lambda: paged.init_block_pool(cfg, e["num_kv_blocks"], 16, 32)),
        jax.eval_shape(lambda: paged.init_block_pool(cfg, e["num_kv_blocks"], 16)),
    ):
        assert pool["k"].shape == pool["v"].shape == (1, 36865, 8, 16, 128)
        assert pool["state"].shape == (3, 33, 64, 128, 128) and pool["conv"].shape == (3, 33, 3, 24576)
        assert nbytes(pool["k"]) + nbytes(pool["v"]) == 36865 * 16 * 4096  # 2.42 GB
        assert nbytes(pool["state"]) + nbytes(pool["conv"]) == 33 * fam.state_bytes_per_slot(c)  # 0.43 GB
        assert nbytes(pool) == 2_845_818_880
    # weights and cache: 59% of the chip's 16 GB
    assert 0.58 < (nbytes(shapes) + nbytes(pool)) / 16e9 < 0.60


def test_decode_step_and_prefill_counts_by_hand():
    c, fam = _published(), harness.family(_published())
    assert fam.experts_touched(c, 1) == pytest.approx(1.0)  # 8 picks, an eighth of them here
    assert 21.5 < fam.experts_touched(c, 32) < 22.5  # "22 of 40 a layer"
    D, V = 4096, 24576
    expert_b = 3 * D * 1280 * 2
    state = fam.state_bytes_per_slot(c)
    # 32 slots at 11,000 positions each, 88 touched experts over the four layers
    ops, nbytes = fam.decode_step(c, 32, 32 * 11000, touched=88)
    assert nbytes == fam.non_expert_weight_bytes(c) + 88 * expert_b + 64 * state + 4096 * (32 * 11000 + 32)
    assert 1.3e9 < fam.non_expert_weight_bytes(c) < 1.5e9  # "mixers and head 1.4"
    assert 2.7e9 < 88 * expert_b < 2.9e9 and 0.82e9 < 64 * state < 0.84e9  # "touched experts 2.8", "state 0.83"
    assert 1.4e9 < 4096 * 32 * 11000 < 1.5e9 and 6.3e9 < nbytes < 6.6e9  # "keys and values 1.4", "about 6.4 GB"
    per_token = 2 * (
        3 * (3 * D * 8192 + 8192 * D + 2 * (D * 128 + 128 * 8192) + D * 64) + 3 * D * 8192 + 2 * D * 1024
        + 4 * (D * 320 + 3 * D * 1280 + 1.0 * 3 * D * 1280)
    )
    scan = 3 * 64 * 7 * 128 * 128
    assert ops == pytest.approx(32 * (per_token + 2 * D * V + scan) + 2 * 64 * 2 * 128 * 32 * 11000)
    assert fam.decode_step(c, 32, 32 * 11000)[1] == pytest.approx(
        fam.decode_step(c, 32, 32 * 11000, touched=4 * fam.experts_touched(c, 32))[1])
    # a fresh chunk: causal in the one GQA layer, the state written once
    T = 2048
    ops, nbytes = fam.prefill(c, T)
    assert ops == pytest.approx(T * (per_token + scan) + 2 * D * V + 2 * 64 * 2 * 128 * T * (T + 1) / 2)
    assert nbytes == fam.weight_bytes(c) + 4096 * T + state
    # a chunk at 8192: its queries see 8193..10240 keys; the rows before it are read, the state read and written
    ops_chunk, bytes_chunk = fam.prefill(c, T, touched=150, start=8192)
    pairs = sum(range(8193, 8193 + T))
    assert ops_chunk == pytest.approx(T * (per_token + scan) + 2 * D * V + 2 * 64 * 2 * 128 * pairs)
    assert bytes_chunk == fam.non_expert_weight_bytes(c) + 150 * expert_b + 4096 * (8192 + T) + 2 * state
    # a prefill is compute-bound, a decode step memory-bound
    peaks = harness.load_json(harness.HERE, "peaks.json")["TPU v5 lite"]
    assert flops_bytes.roofline_pct(ops_chunk, bytes_chunk, 1.0, peaks)[1] == "compute"
    assert flops_bytes.roofline_pct(*fam.decode_step(c, 32, 32 * 11000, touched=88), 1.0, peaks)[1] == "memory"


PEAKS = harness.load_json(harness.HERE, "peaks.json")["TPU v5 lite"]
MS = 1_000_000


def _records():
    step = lambda t, **x: {"phase": "llm.decode_step", "t": t, "dur_s": 0.01, "extra": {"batch": 30, **x}}  # noqa: E731
    rows = dict(kv_rows_live=330000, kv_rows_read=330240, chunks_pending=0, experts_touched=88, picks_here=120, picks=960)
    spans = [
        step(100.0, **{**rows, "kv_rows_live": 1, "chunks_pending": 2}),  # before the trace, inside the window
        step(101.6, **rows),
        step(101.7, **{**rows, "kv_rows_live": 340000, "kv_rows_read": 340256, "chunks_pending": 1}),
        step(101.8, **{**rows, "chunks_pending": 3}),
        {"phase": "llm.prefill_chunk", "t": 101.9, "dur_s": 0.1,
         "extra": {"tokens": 2048, "start": 6144, "bucket": 2048, "experts_touched": 160, "state_carried": 1}},
        {"phase": "llm.prefill_chunk", "t": 102.0, "dur_s": 0.1,
         "extra": {"tokens": 2048, "start": 0, "bucket": 2048, "experts_touched": 158, "state_carried": 0}},
        {"phase": "llm.prefill_chunk", "t": 103.0, "dur_s": 0.1,  # after it
         "extra": {"tokens": 2048, "start": 2048, "bucket": 2048, "experts_touched": 160, "state_carried": 1}},
    ]
    reduced = {"offset_ns": 0, "t0_wall": 101.5, "window_s": 1.0, "program_runs": [
        ["jit_paged_decode(77)", 1000 * MS, 9 * MS], ["jit_paged_prefill(1)", 1010 * MS, 150 * MS],
        ["jit_paged_decode(77)", 1200 * MS, 11 * MS], ["jit_paged_prefill(1)", 1300 * MS, 130 * MS],
    ]}
    requests = [{"prompt_tokens": 8192, "tokens": [101.55 + 0.01 * k for k in range(60)]}]
    return {"spans": spans, "window": [90.0, 135.0], "trace": reduced, "peaks": PEAKS, "config": _published(),
            "requests": requests}


def _least(counts):
    return flops_bytes.roofline_pct(*counts, 1.0, PEAKS)[0] / 100


def test_the_live_row_share_is_a_ratio_of_sums_over_the_traced_steps():
    read = harness.reader("layer_metrics", "kv_live_row_pct")
    rec = _records()
    want = 100.0 * (330000 + 340000 + 330000) / (330240 + 340256 + 330240)
    assert read(rec) == (pytest.approx(want), "%")
    assert read({**rec, "trace": None})[0] != pytest.approx(want)  # the whole window: the step before the trace too
    assert read({**rec, "spans": [{**s, "extra": {"batch": 30}} for s in rec["spans"]]}) is None
    # a cell whose family records other rows reads nothing here
    other = [{**s, "extra": {"batch": 24, "latent_rows_live": 5, "latent_rows_read": 9}} for s in rec["spans"]]
    assert read({**rec, "spans": other}) is None


def test_the_share_of_turns_with_a_chunk_pending_counts_spans_of_the_window():
    read = harness.reader("layer_metrics", "decode_turns_with_chunk_pct")
    rec = _records()
    assert read(rec) == (pytest.approx(100.0 * 3 / 4), "%")  # the measured window, traced or not
    assert read({**rec, "trace": None}) == (pytest.approx(75.0), "%")
    assert read({**rec, "window": [101.65, 135.0]}) == (pytest.approx(100.0), "%")
    assert read({**rec, "spans": [{**s, "extra": {"batch": 30}} for s in rec["spans"]]}) is None
    assert read({**rec, "spans": []}) is None


def test_the_decode_roofline_reader_prices_the_steps_by_this_familys_counts():
    read = harness.reader("layer_metrics", "pg_decode_roofline_pct.kimilinear")
    rec, fam, c = _records(), harness.family(_published()), _published()
    context = sum(8192 + k for k in range(60)) / 3  # the tokens received in the traced second, over its steps
    value, unit = read(rec)
    assert unit == "%" and 0 < value < 100
    assert value == pytest.approx(100.0 * _least(fam.decode_step(c, 30, context, touched=88)) / 0.010)


def test_the_prefill_roofline_reader_prices_a_chunk_at_its_start():
    read = harness.reader("layer_metrics", "pg_prefill_roofline_pct.chunks")
    rec, fam, c = _records(), harness.family(_published()), _published()
    least = [_least(fam.prefill(c, 2048, touched=160, start=6144)), _least(fam.prefill(c, 2048, touched=158))]
    value, unit = read(rec)
    assert unit == "%" and value == pytest.approx(100.0 * (sum(least) / 2) / 0.140) and 0 < value < 100
    assert least[0] > least[1]  # a chunk behind 6k keys costs more than a fresh one
    bare = [{**s, "extra": {"batch": 30, "tokens": 5}} for s in rec["spans"]]
    for without in ({"peaks": None}, {"trace": None}, {"spans": bare}):
        assert read({**rec, **without}) is None
