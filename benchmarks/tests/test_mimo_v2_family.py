"""The ``mimo_v2`` family's files (PR 48): its output check at a size a test run
can hold (the program, driven through the engine with its churn and its prompts
in chunks over window blocks that were given back, passes; the reference in
fp8, without the sink, with unscaled values, every lane rotated, one rotation
base, window layers attending everything, and wronged block tables do not),
the configuration against the catalog, its operation and byte counts (priced
by layer kind, 640 bytes a key/value head a position whatever the pool pads)
against ``init_params``' shapes to the byte and against ISSUE 48's arithmetic,
and the four readers this cell brings, on synthetic records."""

import json
import os

import pytest

from benchmarks import check, flops_bytes, harness

CELL = "serve-longdoc-mimov25"


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


def test_the_program_agrees_with_the_reference_and_its_sinks_matter(program):
    """bf16 weights and activations at the tiny widths, through the engine with
    its churn, in chunks, over blocks that were given back: logits, keys and
    values of both parts; and the sinks take their share of a window's row."""
    _seed, got = program
    assert got["logits_rel_err"] < 0.1 and got["kv_rel_err"] < 0.06, got
    assert got["window_blocks_released"] > 0
    assert 10 < got["sink_share_pct"] < 50


@pytest.mark.parametrize("who, number, times", [
    ("fp8", "logits_rel_err", 3),
    ("no_sink", "logits_rel_err", 3),
    ("unscaled_values", "kv_rel_err", 5),
    ("rope_everywhere", "kv_rel_err", 5),
    ("one_theta", "kv_rel_err", 5),
    ("no_window", "logits_rel_err", 3),
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
            "hybrid_layer_pattern", "moe_layer_freq", "n_routed_experts", "num_hidden_layers", "vocab_size"]
        assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    for key in ("rotation", "value_scale", "score_scale", "sink", "window_edge", "layout_keys", "left_out",
                "router", "cache"):
        assert key in c["assumed"], key
    assert "16 chips share each layer" in c["deployment"] and "layers 6-11" in c["deployment"]
    mix = harness.traffic_of(harness.cell(CELL))
    cfg = harness.family(c).model_config(c, mix)
    # published layer 0 and layers 6-11: the dense layer, then one whole period
    pattern = c["published"]["hybrid_layer_pattern"]
    assert [pattern[0], *pattern[6:12]] == list(cfg.layer_pattern) == [0, 1, 1, 1, 1, 1, 0]
    assert list(cfg.moe_layers) == [0, 1, 1, 1, 1, 1, 1]
    assert (cfg.n_layer, cfg.n_moe_layers, cfg.layers_of(0), cfg.layers_of(1)) == (7, 6, 2, 5)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset, cfg.experts_per_token) == (256, 16, 0, 8)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.swa_n_kv_head) == (4096, 64, 4, 8)
    assert (cfg.head_dim, cfg.v_head_dim, cfg.rotary_dim, cfg.key_lanes) == (192, 128, 64, 256)
    assert (cfg.d_ff, cfg.moe_d_ff, cfg.sliding_window, cfg.routed_scaling) == (16384, 2048, 128, 1.0)
    assert (cfg.rope_theta, cfg.swa_rope_theta, cfg.value_scale) == (1e7, 1e4, 0.707)
    assert (cfg.swa_sink, cfg.full_sink, cfg.silent_ids) == (True, False, (257,))
    assert (cfg.max_seq, cfg.window_slots, cfg.prefill_span) == (18432, 32, 2048)
    # the floors: a whole period and four layers behind the dense one, 8 experts, an eighth of the vocabulary
    assert cfg.n_moe_layers >= 4 and c["n_routed_experts"] >= 8
    assert c["vocab_size"] * 8 >= c["published"]["vocab_size"]
    # the mix is the accepted file
    assert mix["clients"] == mix["engine"]["max_slots"] == 32 and mix["kind"] == "closed-loop"
    assert min(mix["prompt_tokens"]) == 6144 and max(mix["prompt_tokens"]) == 16384
    cell = harness.cell(CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and "7 of 48 layers" in cell["why"]


def test_weight_and_cache_bytes_by_hand_and_by_the_shapes_the_program_draws():
    """ISSUE 48's arithmetic, in this repo's bytes (bf16 2 B; the router, its
    bias and the sinks float32)."""
    c, fam = _published(), harness.family(_published())
    D, V = 4096, 19072
    window = D * 64 * 192 + D * 8 * 192 + D * 8 * 128 + 64 * 128 * D
    full = D * 64 * 192 + D * 4 * 192 + D * 4 * 128 + 64 * 128 * D
    assert round(window / 1e5) == 944 and round(full / 1e5) == 891  # 94.4 M and 89.1 M
    dense, expert = 3 * D * 16384, 3 * D * 2048
    assert round(dense / 1e5) == 2013 and round(16 * expert / 1e5) == 4027  # 201.3 M; 16 experts 402.7 M
    router = (D * 256 + 256) * 4
    non_expert = 2 * (2 * full + 5 * window + 7 * 2 * D + dense + D + D * V) + 6 * router + 5 * 64 * 4
    assert fam.non_expert_weight_bytes(c) == non_expert
    assert fam.weight_bytes(c) == non_expert + 6 * 16 * expert * 2
    assert fam.kv_bytes_per_token(c) == 2 * 2560 + 5 * 5120 == 30720  # 640 B a key/value head
    import jax

    from ray_tpu.models import paged

    mix = harness.traffic_of(harness.cell(CELL))
    cfg = fam.model_config(c, mix)
    shapes = jax.eval_shape(lambda k: fam.init_params(k, cfg), jax.random.key(0))
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))  # noqa: E731
    assert nbytes(shapes) == fam.weight_bytes(c) + V * D * 2 == 6_872_497_408  # 6.9 GB
    # the pool, as the engine counts the window part and as the rehearsal does from the configuration
    e = mix["engine"]
    assert paged.window_blocks_a_slot(128, 2048, 16) == 137  # the chunk's span, 17 times the window
    for pool in (
        jax.eval_shape(lambda: paged.init_block_pool(cfg, e["num_kv_blocks"], 16, 32, window_blocks=32 * 137 + 1)),
        jax.eval_shape(lambda: paged.init_block_pool(cfg, e["num_kv_blocks"], 16)),
    ):
        assert pool["full"]["k"].shape == (2, 36865, 4, 16, 256) and pool["full"]["v"].shape == (2, 36865, 4, 16, 128)
        assert pool["window"]["k"].shape == (5, 4385, 8, 16, 256) and pool["window"]["v"].shape == (5, 4385, 8, 16, 128)
        # as laid, a key in 256 lanes: 3,072 and 6,144 B a position
        assert nbytes(pool["full"]) == 36865 * 16 * 2 * 3072 == 3_623_976_960
        assert nbytes(pool["window"]) == 4385 * 16 * 5 * 6144 == 2_155_315_200
    # as the mathematics needs it: 2,560 and 5,120 B a position
    needed = 36865 * 16 * 2 * 2560 + 4385 * 16 * 5 * 5120
    assert needed == 4_816_076_800 and nbytes(pool) / needed == pytest.approx(1.2)
    assert 0.75 < (nbytes(shapes) + nbytes(pool)) / 16e9 < 0.82  # ISSUE 48: about 79% of the chip


def test_decode_step_and_prefill_counts_by_hand_priced_by_kind():
    c, fam = _published(), harness.family(_published())
    assert fam.experts_touched(c, 1) == pytest.approx(0.5)  # 8 picks, a sixteenth of them here
    assert 9.5 < fam.experts_touched(c, 30) < 10.0
    expert_b = 3 * 4096 * 2048 * 2
    # thirty slots at 11,000 positions
    rows_full, rows_window = 30 * 11000, 30 * 128
    attn_ops, attn_bytes = fam.attention_decode(c, rows_full, rows_window)
    assert attn_bytes == 2 * rows_full * 2560 + 5 * rows_window * 5120
    assert attn_ops == 2 * 64 * 320 * (2 * rows_full + 5 * rows_window)
    assert 1.6e9 < fam.attention_decode(c, rows_full, 0)[1] < 1.8e9  # "1.8 GB of full rows"
    assert 0.09e9 < fam.attention_decode(c, 0, rows_window)[1] < 0.11e9  # "0.1 GB of window rows"
    assert fam.attention_decode(c, rows_full, 0)[1] + fam.attention_decode(c, 0, rows_window)[1] == attn_bytes
    ops, nbytes = fam.decode_step(c, 30, rows_full, touched=57, rows_window=rows_window)
    assert nbytes == fam.non_expert_weight_bytes(c) + 57 * expert_b + attn_bytes + 30 * 30720
    assert 4.4e9 < fam.non_expert_weight_bytes(c) + 57 * expert_b < 5.0e9  # "4.8 GB of weights"
    D = 4096
    window = D * 64 * 320 + D * 8 * 320
    full = D * 64 * 320 + D * 4 * 320
    per_token = 2 * (2 * full + 5 * window + 3 * D * 16384 + 6 * (D * 256 + 0.5 * 3 * D * 2048))
    assert ops == pytest.approx(30 * (per_token + 2 * D * 19072) + attn_ops)
    # without the spans' rows: every sequence at the mean context, a window layer's capped
    assert fam.decode_step(c, 30, rows_full)[1] == pytest.approx(fam.decode_step(c, 30, rows_full, rows_window=rows_window)[1])
    # a fresh chunk: causal in a full layer, 128 keys at most in a window layer
    T = 2048
    ops, nbytes = fam.prefill(c, T)
    pairs_win = 128 * 129 / 2 + 128 * (T - 128)
    assert ops == pytest.approx(T * per_token + 2 * D * 19072 + 2 * 64 * 320 * (2 * T * (T + 1) / 2 + 5 * pairs_win))
    assert nbytes == fam.weight_bytes(c) + 30720 * T
    assert 3.5e12 < T * per_token < 4.1e12  # "3.8 TFLOP of projections and experts"
    # a chunk at 8192: a full layer's queries see 8193..10240 keys, a window layer's 128 each
    ops_chunk, bytes_chunk = fam.prefill(c, T, touched=90, start=8192)
    pairs_full = sum(range(8193, 8193 + T))
    assert ops_chunk == pytest.approx(T * per_token + 2 * D * 19072 + 2 * 64 * 320 * (2 * pairs_full + 5 * T * 128))
    assert bytes_chunk == fam.non_expert_weight_bytes(c) + 90 * expert_b + 30720 * T + 2 * 8192 * 2560 + 5 * 127 * 5120
    attn = lambda start: fam.prefill(c, T, start=start)[0] - T * per_token - 2 * D * 19072  # noqa: E731
    # ISSUE 48: "1.0-2.7 TFLOP of full attention at 6k-16k keys"; the window layers' 0.05 with it
    assert 0.9e12 < attn(4096) < 1.0e12 and 2.5e12 < attn(14336) < 2.8e12


PEAKS = harness.load_json(harness.HERE, "peaks.json")["TPU v5 lite"]
MS = 1_000_000


def _records():
    step = lambda t, **x: {"phase": "llm.decode_step", "t": t, "dur_s": 0.01, "extra": {"batch": 30, **x}}  # noqa: E731
    rows = dict(kv_rows_full=330000, kv_rows_window=3840, kv_rows_window_read=4320, experts_touched=57)
    spans = [
        step(100.0, **{**rows, "kv_rows_full": 1}),  # before the trace
        step(101.6, **rows),
        step(101.7, **{**rows, "kv_rows_full": 350000, "kv_rows_window": 3700}),
    ]
    reduced = {"offset_ns": 0, "t0_wall": 101.5, "window_s": 1.0, "program_runs": [
        ["jit_paged_decode(77)", 1000 * MS, 9 * MS], ["jit_paged_prefill(1)", 1010 * MS, 150 * MS],
        ["jit_paged_decode(77)", 1200 * MS, 11 * MS],
    ], "ops": [["fusion.1", 0.5], ["paged_decode_attention_full.3", 0.004], ["paged_decode_attention_full.4", 0.0012],
               ["paged_decode_attention_window.5", 0.0016]]}
    stats = {"cache_bytes_full_k": 100, "cache_bytes_laid_full_k": 256, "cache_bytes_laid_full_v": 128,
             "cache_bytes_laid_window_k": 512, "cache_bytes_laid_window_v": 256,
             "cache_bytes_needed_kind0": 320, "cache_bytes_needed_kind1": 640}
    return {"spans": spans, "window": [90.0, 135.0], "trace": reduced, "peaks": PEAKS, "config": _published(),
            "engine_stats": stats}


def _least(counts):
    return flops_bytes.roofline_pct(*counts, 1.0, PEAKS)[0] / 100


@pytest.mark.parametrize("kind, kernel_s, rows", [
    ("full", 0.0052, lambda full, window: (full, 0)),
    ("window", 0.0016, lambda full, window: (0, window)),
])
def test_each_kinds_kernel_reader_prices_its_own_rows_over_its_own_calls(kind, kernel_s, rows):
    read = harness.reader("layer_metrics", f"attn_decode_kernel_roofline_pct.{kind}")
    rec, fam, c = _records(), harness.family(_published()), _published()
    least = [_least(fam.attention_decode(c, *rows(330000, 3840))), _least(fam.attention_decode(c, *rows(350000, 3700)))]
    value, unit = read(rec)
    assert unit == "%" and value == pytest.approx(100.0 * (sum(least) / 2) / (kernel_s / 2)) and 0 < value < 100
    # another family's calls carry no kind: nothing to read; nor without a trace, peaks, runs or rows
    unnamed = [["fusion.1", 0.5], ["paged_decode_attention.3", 0.004]]
    others = [r for r in rec["trace"]["program_runs"] if not r[0].startswith("jit_paged_decode")]
    bare = [{**s, "extra": {"batch": 30}} for s in rec["spans"]]
    for without in ({"trace": {**rec["trace"], "ops": unnamed}}, {"peaks": None}, {"trace": None}, {"spans": bare},
                    {"trace": {**rec["trace"], "program_runs": others}}, {"trace": {**rec["trace"], "t0_wall": None}},
                    {"config": harness.config_of(harness.cell("serve-batch-mistral7b"))}):
        assert read({**rec, **without}) is None


def test_the_lumped_kernel_reader_still_finds_the_named_calls_by_prefix():
    read = harness.reader("layer_metrics", "attn_decode_kernel_roofline_pct")
    rec, fam, c = _records(), harness.family(_published()), _published()
    least = [_least(fam.attention_decode(c, 330000, 3840)), _least(fam.attention_decode(c, 350000, 3700))]
    assert read(rec)[0] == pytest.approx(100.0 * (sum(least) / 2) / (0.0068 / 2))


def test_the_share_of_decode_time_inside_the_kernel_and_the_pools_padding():
    rec = _records()
    read = harness.reader("layer_metrics", "decode_attn_time_pct")
    assert read(rec) == (pytest.approx(100.0 * 0.0068 / 0.020), "%")
    assert read({**rec, "trace": None}) is None
    assert read({**rec, "trace": {**rec["trace"], "ops": [["fusion.1", 0.5]]}}) is None  # the gather
    pad = harness.reader("layer_metrics", "kv_pool_pad_pct")
    assert pad(rec) == (pytest.approx(20.0), "%")  # 1152 laid over 960 needed
    assert pad({**rec, "trace": None}) == (pytest.approx(20.0), "%")  # a counter: no trace needed
    assert pad({**rec, "engine_stats": {"cache_bytes_full_k": 100}}) is None  # a commit from before the counters
    assert pad({**rec, "engine_stats": None}) is None
