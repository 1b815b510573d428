"""The ``afmoe`` family's files (PR 40): its output check at a size a test run
can hold (the program, driven through the engine, passes; the reference in fp8,
with sliding layers attending everything, full layers rotated or the gate left
out, and wronged block tables do not), the configuration against the catalog,
its operation and byte counts (the window counted) against ``init_params``'
shapes to the byte and against numbers reckoned by hand, and the five readers
this cell brings, on synthetic records."""

import json
import os

import pytest

from benchmarks import check, flops_bytes, harness

CELL = "serve-mixed-trinity"
S, F = "sliding_attention", "full_attention"


def _tiny():
    return harness.cell_files(harness.cell(CELL), rehearsal=1)


def _published():
    return harness.config_of(harness.cell(CELL))


# The weights' norms over q and k make attention sharp (afmoe.draw_params), and
# at the tiny widths (heads of 16, a hidden state of 64) bfloat16 then reads
# 0.07-0.23 by the logits over eight seeds, a flipped pick of an expert or two
# among them; these two read lowest. The chip's readings at the served widths
# are PERF.md section 2's.
@pytest.fixture(scope="module", params=[3, 3000000015])
def program(request):
    c, mix = _tiny()
    return request.param, check.check_one(c, mix, request.param, "program")


def test_the_program_agrees_with_the_reference(program):
    """bf16 weights and activations at the tiny widths, through the engine with
    its churn, over blocks that were given back: logits, keys and values."""
    _seed, got = program
    assert got["logits_rel_err"] < 0.1 and got["kv_rel_err"] < 0.06
    assert got["window_blocks_released"] > 0


@pytest.mark.parametrize("who, number, times", [
    ("fp8", "logits_rel_err", 3),
    ("no_window", "logits_rel_err", 10),
    ("rope_everywhere", "logits_rel_err", 3),
    ("ungated", "logits_rel_err", 3),
    ("swapped_tables", "kv_rel_err", 5),
    ("displaced", "kv_rel_err", 5),
])
def test_every_control_is_outside_a_number_the_program_is_inside(program, who, number, times):
    seed, right = program
    c, mix = _tiny()
    wrong = check.check_one(c, mix, seed, who)
    assert set(wrong) >= {"logits_rel_err", "kv_rel_err"}
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
        assert differ == sorted(c["reduced"]) == [
            "layer_types", "num_dense_layers", "num_experts", "num_hidden_layers", "vocab_size"]
        assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    for key in ("output_gate", "head_norms", "rotation", "embedding_multiplier", "window_edge",
                "depth_scaling", "smebu"):
        assert key in c["assumed"], key
    assert "8 chips share each layer" in c["deployment"] and "stages of 5" in c["deployment"]
    mix = harness.traffic_of(harness.cell(CELL))
    cfg = harness.family(c).model_config(c, mix)
    # published layers 5-9: the last dense layer and one whole period of the expert layers
    assert c["published"]["layer_types"][5:10] == list(cfg.layer_types) == [S, S, F, S, S]
    assert (cfg.n_layer, cfg.n_dense, cfg.n_moe_layers) == (5, 1, 4)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset, cfg.experts_per_token) == (256, 32, 0, 4)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim) == (3072, 48, 8, 128)
    assert (cfg.d_ff, cfg.moe_d_ff, cfg.sliding_window, cfg.routed_scaling) == (12288, 3072, 4096, 2.448)
    assert (cfg.max_seq, cfg.window_slots, cfg.prefill_span, cfg.mup) == (18432, 24, 2048, True)
    assert c["num_experts"] >= 8 and c["vocab_size"] * 8 >= c["published"]["vocab_size"]
    # the mix is the issue's, letter for letter
    assert mix["clients"] == mix["engine"]["max_slots"] == 24 and mix["kind"] == "closed-loop"
    assert mix["prompt_tokens"] == [256, 512, 768, 1024, 1280, 1536, 1792, 2048,
                                    6144, 7168, 8192, 10240, 11264, 12288, 14336, 15360]
    assert mix["output_tokens"] == [256, 320, 384, 448, 512, 576, 640, 704, 768, 768, 832, 896, 960, 1024, 1024, 1024]
    e = mix["engine"]
    assert (e["max_seq"], e["kv_block_size"], e["num_kv_blocks"], e["prefill_chunk_tokens"]) == (18432, 16, 24577, 2048)
    assert e["prefill_buckets"] == [32, 64, 128, 256, 512, 1024, 2048, 8192, 16384]
    cell = harness.cell(CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and len(c["source"]) <= 200


def test_weight_and_cache_bytes_by_hand_and_by_the_shapes_the_program_draws():
    """ISSUE 40's arithmetic, in this repo's bytes (bf16 2 B; the router and
    its bias float32)."""
    c, fam = _published(), harness.family(_published())
    D, V = 3072, 25024
    attention = 3 * D * 6144 + 2 * D * 1024  # 62.9 M with the gate
    assert round(attention / 1e5) == 629
    small = 2 * 128 + 4 * D  # the heads' norms, the sandwich's
    dense, expert = 3 * D * 12288, 3 * D * 3072  # 113.2 M, 28.3 M
    router = (D * 256 + 256) * 4
    non_expert = 2 * (5 * (attention + small) + dense + 4 * expert + D + D * V) + 4 * router
    assert fam.non_expert_weight_bytes(c) == non_expert
    assert fam.weight_bytes(c) == non_expert + 4 * 32 * expert * 2
    assert fam.kv_bytes_per_token(c) == 5 * 2 * 8 * 128 * 2 == 20480
    import jax

    from ray_tpu.models import paged

    mix = harness.traffic_of(harness.cell(CELL))
    cfg = fam.model_config(c, mix)
    shapes = jax.eval_shape(lambda k: fam.init_params(k, cfg), jax.random.key(0))
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))  # noqa: E731
    assert nbytes(shapes) == fam.weight_bytes(c) + V * D * 2 == 8_650_101_248  # 8.65 GB
    assert round(sum(x.size for x in jax.tree.leaves(shapes)) / 1e6) == 4322  # 4.32 B parameters
    # the pool, as the engine counts the window part and as the rehearsal does from the configuration
    e = mix["engine"]
    per_slot = paged.window_blocks_a_slot(4096, 2048, 16)
    assert per_slot == 385
    for pool in (
        jax.eval_shape(lambda: paged.init_block_pool(cfg, e["num_kv_blocks"], 16, 24, window_blocks=24 * 385 + 1)),
        jax.eval_shape(lambda: paged.init_block_pool(cfg, e["num_kv_blocks"], 16)),
    ):
        assert pool["full"]["k"].shape == (1, 24577, 8, 16, 128)
        assert pool["window"]["v"].shape == (4, 24 * 385 + 1, 8, 16, 128)
        assert nbytes(pool["full"]) == 24577 * 65536 and nbytes(pool["window"]) == 4 * 9241 * 65536
        assert nbytes(pool) == 4_033_150_976  # 1.61 + 2.42 GB
    # with every layer keeping every position the same slots would need five times the full part
    assert 5 * nbytes(pool["full"]) + nbytes(shapes) > 16.6e9


def test_decode_step_and_prefill_counts_by_hand_with_the_window_counted():
    c, fam = _published(), harness.family(_published())
    assert fam.experts_touched(c, 1) == pytest.approx(0.5)  # 4 picks, an eighth of them here
    assert 9.9 < fam.experts_touched(c, 24) < 10.1  # "some 10 touched experts of 32 a layer"
    expert_b, kv_layer = 3 * 3072 * 3072 * 2, 2 * 8 * 128 * 2
    # twelve slots at 11,000 positions and twelve at 1,500
    rows_full, rows_window = 12 * 11000 + 12 * 1500, 12 * 4096 + 12 * 1500
    ops, nbytes = fam.decode_step(c, 24, rows_full, touched=40, rows_window=rows_window)
    attn_ops, attn_bytes = fam.attention_decode(c, rows_full, rows_window)
    assert attn_bytes == kv_layer * (rows_full + 4 * rows_window) and attn_ops == 2 * 48 * 2 * 128 * (rows_full + 4 * rows_window)
    assert nbytes == fam.non_expert_weight_bytes(c) + 40 * expert_b + attn_bytes + 24 * 20480
    assert 1.4e9 < attn_bytes < 1.8e9  # "about 1.6 GB of keys and values"
    assert fam.attention_decode(c, rows_full, rows_full)[1] == pytest.approx(3.07e9, rel=0.02)  # five full layers: 3.1 GB
    assert 3.2e9 < fam.non_expert_weight_bytes(c) + 40 * expert_b < 3.7e9  # "about 3.5 GB of weights"
    # without the spans' rows: every sequence at the mean context
    assert fam.decode_step(c, 24, 24 * 3000)[1] == pytest.approx(
        fam.decode_step(c, 24, 24 * 3000, rows_window=24 * 3000)[1])
    assert fam.decode_step(c, 24, 24 * 9000, touched=40)[1] == fam.decode_step(c, 24, 24 * 9000, 40, 24 * 4096)[1]
    D = 3072
    per_token = 2 * (5 * (3 * D * 6144 + 2 * D * 1024) + 3 * D * 12288 + 4 * (D * 256 + 3 * D * 3072 + 0.5 * 3 * D * 3072))
    assert ops == pytest.approx(24 * (per_token + 2 * D * 25024) + attn_ops)
    # a fresh prompt shorter than the window: causal in every layer
    T = 2048
    ops, nbytes = fam.prefill(c, T)
    assert ops == pytest.approx(T * per_token + 2 * D * 25024 + 2 * 48 * 2 * 128 * 5 * T * (T + 1) / 2)
    assert nbytes == fam.weight_bytes(c) + 20480 * T
    # a chunk at 8192: a full layer's queries see 8193..10240 keys, a window layer's 4096 each
    ops_chunk, bytes_chunk = fam.prefill(c, T, touched=100, start=8192)
    pairs_full = sum(range(8193, 8193 + T))
    assert ops_chunk == pytest.approx(T * per_token + 2 * D * 25024 + 2 * 48 * 2 * 128 * (pairs_full + 4 * T * 4096))
    assert bytes_chunk == fam.non_expert_weight_bytes(c) + 100 * expert_b + 20480 * T + kv_layer * (8192 + 4 * 4095)
    # "a long prompt's attention is a third of its prefill's arithmetic"
    whole = sum(fam.prefill(c, T, start=s)[0] for s in range(0, 12288, T))
    matmul = 12288 * per_token
    assert 0.25 < (whole - matmul) / whole < 0.45


PEAKS = harness.load_json(harness.HERE, "peaks.json")["TPU v5 lite"]
MS = 1_000_000


def _records():
    step = lambda t, **x: {"phase": "llm.decode_step", "t": t, "dur_s": 0.01, "extra": {"batch": 24, **x}}  # noqa: E731
    rows = dict(kv_rows_full=150000, kv_rows_window=67000, kv_rows_window_read=68000,
                window_blocks_held=4200, blocks_full_retention=9400, experts_touched=40, picks_here=50)
    spans = [
        step(100.0, **{**rows, "kv_rows_window": 1}),  # before the trace
        step(101.6, **rows),
        step(101.7, **{**rows, "kv_rows_full": 170000, "kv_rows_window": 69000, "kv_rows_window_read": 70500,
                       "window_blocks_held": 4300, "blocks_full_retention": 10600}),
        {"phase": "llm.prefill", "t": 101.8, "dur_s": 0.1, "extra": {"tokens": 700, "reused": 0, "bucket": 1024, "experts_touched": 128}},
        {"phase": "llm.prefill_chunk", "t": 102.0, "dur_s": 0.1, "extra": {"tokens": 2048, "start": 6144, "bucket": 2048, "experts_touched": 128}},
        {"phase": "llm.prefill_chunk", "t": 103.0, "dur_s": 0.1, "extra": {"tokens": 2048, "start": 0, "bucket": 2048, "experts_touched": 128}},  # after it
    ]
    reduced = {"offset_ns": 0, "t0_wall": 101.5, "window_s": 1.0, "program_runs": [
        ["jit_paged_decode(77)", 1000 * MS, 9 * MS], ["jit_paged_prefill(1)", 1010 * MS, 150 * MS],
        ["jit_paged_decode(77)", 1200 * MS, 11 * MS], ["jit_paged_prefill(2)", 1300 * MS, 250 * MS],
    ], "ops": [["fusion.1", 0.5], ["paged_decode_attention.3", 0.004], ["paged_decode_attention.4", 0.0008]]}
    return {"spans": spans, "window": [90.0, 135.0], "trace": reduced, "peaks": PEAKS, "config": _published()}


def _least(counts):
    return flops_bytes.roofline_pct(*counts, 1.0, PEAKS)[0] / 100


def test_the_decode_roofline_reader_prices_each_step_by_its_spans_rows():
    read = harness.reader("layer_metrics", "pg_decode_roofline_pct.window")
    rec, fam, c = _records(), harness.family(_published()), _published()
    least = [_least(fam.decode_step(c, 24, 150000, touched=40, rows_window=67000)),
             _least(fam.decode_step(c, 24, 170000, touched=40, rows_window=69000))]
    value, unit = read(rec)
    assert unit == "%" and value == pytest.approx(100.0 * (sum(least) / 2) / 0.010) and 0 < value < 100
    _reads_nothing(read, rec, "jit_paged_decode")


def test_the_prefill_roofline_reader_prices_a_chunk_at_its_start():
    read = harness.reader("layer_metrics", "pg_prefill_roofline_pct.chunks")
    rec, fam, c = _records(), harness.family(_published()), _published()
    least = [_least(fam.prefill(c, 700, touched=128)), _least(fam.prefill(c, 2048, touched=128, start=6144))]
    value, unit = read(rec)
    assert unit == "%" and value == pytest.approx(100.0 * (sum(least) / 2) / 0.200) and 0 < value < 100
    assert least[1] > _least(fam.prefill(c, 2048, touched=128))  # a chunk behind 6k keys costs more than a fresh one
    _reads_nothing(read, rec, "jit_paged_prefill")
    assert read({**rec, "config": harness.config_of(harness.cell("serve-chat-nemotron3super"))}) is None


def test_the_attention_kernels_reader_by_operation_name_over_the_steps_bytes():
    read = harness.reader("layer_metrics", "attn_decode_kernel_roofline_pct")
    rec, fam, c = _records(), harness.family(_published()), _published()
    least = [_least(fam.attention_decode(c, 150000, 67000)), _least(fam.attention_decode(c, 170000, 69000))]
    value, unit = read(rec)
    assert unit == "%" and value == pytest.approx(100.0 * (sum(least) / 2) / (0.0048 / 2)) and 0 < value < 100
    _reads_nothing(read, rec, "jit_paged_decode")
    assert read({**rec, "trace": {**rec["trace"], "ops": [["fusion.1", 0.5]]}}) is None  # the gather: no such operation


def _reads_nothing(read, rec, program):
    others = [r for r in rec["trace"]["program_runs"] if not r[0].startswith(program)]
    bare = [{**s, "extra": {"batch": 24, "tokens": 5}} for s in rec["spans"]]
    for without in ({"peaks": None}, {"trace": None}, {"spans": bare},
                    {"trace": {**rec["trace"], "program_runs": others}},
                    {"trace": {**rec["trace"], "t0_wall": None}}):
        assert read({**rec, **without}) is None


@pytest.mark.parametrize("name, want", [
    ("window_live_row_pct", 100.0 * (67000 + 69000) / (68000 + 70500)),
    ("window_blocks_held_pct", 100.0 * (4200 + 4300) / (9400 + 10600)),
])
def test_the_two_counter_readers_are_ratios_of_sums_over_the_traced_steps(name, want):
    read = harness.reader("layer_metrics", name)
    rec = _records()
    assert read(rec) == (pytest.approx(want), "%")
    untraced = {**rec, "trace": None}  # the whole window then: the step before the trace counts too
    assert read(untraced)[0] != pytest.approx(want)
    assert read({**rec, "spans": [{**s, "extra": {"batch": 24}} for s in rec["spans"]]}) is None
