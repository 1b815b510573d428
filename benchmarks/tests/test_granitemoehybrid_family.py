"""The ``granitemoehybrid`` family's files (PR 51): its output check at a size a
test run can hold (the program, driven through the engine, passes; the
reference in fp8 or with a multiplier, the tie or the one group taken away,
wronged block tables and exchanged states do not), the configuration against
the catalog with nothing cut, its operation and byte counts against
``init_params``' shapes to the byte and against numbers reckoned by hand, and
the four readers the cell brings."""

import json
import os

import pytest

from benchmarks import check, flops_bytes, harness

CELL = "serve-chat-granite4hmicro"


def _tiny():
    return harness.cell_files(harness.cell(CELL), rehearsal=1)


def _published():
    return harness.config_of(harness.cell(CELL))


@pytest.fixture(scope="module", params=[1, 3000000011])
def program(request):
    c, mix = _tiny()
    return request.param, check.check_one(c, mix, request.param, "program")


def test_the_program_agrees_with_the_reference(program):
    """bf16 weights and activations at the tiny widths, through the engine with
    its churn: logits, states and tails, keys and values."""
    _seed, got = program
    assert got["logits_rel_err"] < 0.02 and got["state_rel_err"] < 0.02 and got["kv_rel_err"] < 0.02


@pytest.mark.parametrize("who, number, times", [
    ("fp8", "logits_rel_err", 3),
    ("no_residual_multiplier", "logits_rel_err", 10),
    ("no_embedding_multiplier", "logits_rel_err", 10),
    ("unscaled_logits", "logits_rel_err", 10),
    ("untied", "logits_rel_err", 10),
    ("eight_groups", "logits_rel_err", 3),
    ("stale_state", "state_rel_err", 10),
    ("swapped_tables", "kv_rel_err", 10),
    ("displaced", "kv_rel_err", 10),
])
def test_every_control_is_outside_a_number_the_program_is_inside(program, who, number, times):
    seed, right = program
    c, mix = _tiny()
    wrong = check.check_one(c, mix, seed, who)
    assert set(wrong) >= {"logits_rel_err", "state_rel_err", "kv_rel_err"}
    assert wrong[number] > times * right[number], (who, wrong, right)


def test_an_unknown_control_is_refused():
    c, mix = _tiny()
    with pytest.raises(SystemExit, match="unknown --who"):
        check.check_one(c, mix, 1, "int4")


def test_the_configuration_is_the_published_one_and_nothing_is_cut():
    c = _published()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["source_url"] == c["source"])
        assert [k for k, v in row["config"].items() if c.get(k) != v] == [] == c["reduced"]
    mix = harness.traffic_of(harness.cell(CELL))
    cfg = harness.family(c).model_config(c, mix)
    assert cfg.n_layer == 40 and cfg.periods == 4
    assert cfg.period == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert [i for i, k in enumerate(cfg.layer_types) if k == "attention"] == [5, 15, 25, 35]
    assert (cfg.d_model, cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_groups, cfg.ssm_state) == (2048, 64, 64, 1, 128)
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.conv_kernel, cfg.d_ff) == (32, 8, 64, 4, 8192)
    assert (cfg.attention_multiplier, cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling) == (
        1 / 64, 12.0, 0.22, 8.0)
    assert cfg.vocab_size == 100352 and cfg.state_slots == 64 and cfg.max_seq == 2048
    assert set(c["assumed"]) == {"mamba", "mlp", "attention", "weights", "tokenizer"} and "one chip" in c["deployment"]
    # the mix is the accepted one, unedited: Nemotron's cell runs it too
    assert mix["clients"] == mix["engine"]["max_slots"] == 64 and mix["kind"] == "closed-loop"
    assert (mix["engine"]["max_seq"], mix["engine"]["kv_block_size"], mix["engine"]["num_kv_blocks"]) == (2048, 16, 8193)


def test_weight_cache_and_state_bytes_by_hand_and_by_the_shapes_the_program_draws():
    """ISSUE 51's arithmetic, in this repo's bytes (bf16 2 B; the state-space
    scalars and the state float32)."""
    c, fam = _published(), harness.family(_published())
    D, V, F = 2048, 100352, 8192
    mamba = D * (4096 + 4352 + 64) + 4096 * D  # 25.8 M in matrices
    mamba_small = 5 * 4352 + 4096  # the convolution and its bias, the gated norm
    attention = 2 * D * 2048 + 2 * D * 512  # 10.5 M
    mlp = 3 * D * F  # 50.3 M
    params = 36 * (mamba + mamba_small + 3 * 64) + 4 * attention + 40 * (mlp + 2 * D) + D + V * D
    assert fam.num_params(c) == params and round(params / 1e7) == 319  # 3.19 B
    assert fam.weight_bytes(c) == 2 * params + 36 * 3 * 64 * 2  # the float32 scalars cost 2 B more each
    assert 6.38e9 < fam.weight_bytes(c) < 6.39e9
    assert fam.kv_bytes_per_token(c) == 4 * 2 * 8 * 64 * 2 == 8192  # whatever the pool pads
    assert fam.state_step_bytes_per_slot(c) == 36 * 64 * 64 * 128 * 4 == 36 * 2 * 2**20
    assert fam.state_bytes_per_slot(c) == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert 75.4e6 < fam.state_bytes_per_slot(c) < 76.5e6  # 75.5 MB of state, and the tails
    # the parameters the program draws: the counts above, to the byte
    import jax

    from ray_tpu.models import paged

    mix = harness.traffic_of(harness.cell(CELL))
    cfg = fam.model_config(c, mix)
    shapes = jax.eval_shape(lambda k: fam.init_params(k, cfg), jax.random.key(0))
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))  # noqa: E731
    assert sum(x.size for x in jax.tree.leaves(shapes)) == params
    assert nbytes(shapes) == fam.weight_bytes(c)
    # the pool the engine makes, against the same counts
    e = mix["engine"]
    pool = jax.eval_shape(lambda: paged.init_block_pool(cfg, e["num_kv_blocks"], e["kv_block_size"], e["max_slots"]))
    assert pool["state"].shape == (36, 65, 64, 64, 128) and pool["conv"].shape == (36, 65, 3 * 4352)
    assert pool["kv"].shape == (4, 8193, 8, 16, 128)
    assert nbytes({k: pool[k] for k in ("state", "conv")}) == 65 * fam.state_bytes_per_slot(c)
    assert nbytes(pool["kv"]) == 8193 * 16 * fam.kv_bytes_per_token(c)  # nothing padded
    assert 4.90e9 < nbytes(pool["state"]) < 4.91e9 and 1.07e9 < nbytes(pool["kv"]) < 1.08e9
    assert 12.4e9 < nbytes(shapes) + nbytes(pool) < 12.5e9  # 77% of the chip's 16 GB


def test_decode_step_prefill_and_attention_counts_by_hand():
    c, fam = _published(), harness.family(_published())
    batch, context = 64, 64 * 400
    ops, nbytes = fam.decode_step(c, batch, context)
    assert nbytes == fam.weight_bytes(c) + 2 * 64 * fam.state_bytes_per_slot(c) + 8192 * (context + batch)
    assert 16.0e9 < nbytes < 16.5e9  # ISSUE 51: 6.4 + 9.7 + 0.2 GB a step
    assert 0.58 < 2 * 64 * fam.state_step_bytes_per_slot(c) / nbytes < 0.61  # the state is three fifths of it
    D = 2048
    per_token = 2 * (36 * (D * 8512 + 4096 * D) + 4 * (2 * D * 2048 + 2 * D * 512) + 40 * 3 * D * 8192)
    recurrence = 36 * 64 * 5 * 64 * 128
    attn = lambda rows: 2 * 4 * 32 * 2 * 64 * rows  # noqa: E731
    assert ops == pytest.approx(batch * (per_token + 2 * D * 100352 + recurrence) + attn(context))
    assert nbytes / 819e9 > 5 * ops / 197e12  # memory-bound: the share is of bytes
    assert fam.attention_decode(c, context) == (attn(context), 8192 * context)
    T = 512
    ops, nbytes = fam.prefill(c, T)
    assert ops == pytest.approx(T * (per_token + recurrence) + 2 * D * 100352 + attn(T * (T + 1) / 2))
    assert 6.0e9 < per_token + recurrence < 6.1e9  # 6.06 GFLOP a prompt token (ISSUE 51's 6.4 has the head on every one)
    assert nbytes == fam.weight_bytes(c) + 8192 * T + fam.state_bytes_per_slot(c)


def _records(**extra_on_steps):
    c = _published()
    peaks = harness.load_json(harness.HERE, "peaks.json")["TPU v5 lite"]
    ms = 1_000_000
    reduced = {"offset_ns": 0, "t0_wall": 101.5, "window_s": 1.0, "program_runs": [
        ["jit_paged_decode(77)", 1000 * ms, 30 * ms],
        ["jit_paged_prefill(123)", 1040 * ms, 60 * ms],
        ["jit_paged_decode(77)", 1110 * ms, 30 * ms],
    ], "ops": [["state_step_ssd.3", 0.036], ["paged_decode_attention_packed.7", 0.002], ["fusion.9", 0.1]]}
    step = {"batch": 60, "state_slots": 60, "state_layers": 36, "kv_blocks_live": 1800, **extra_on_steps}
    spans = [
        {"phase": "llm.decode_step", "t": 100.0, "dur_s": 0.03, "extra": {**step, "state_slots": 3}},  # before the trace
        {"phase": "llm.decode_step", "t": 101.6, "dur_s": 0.03, "extra": step},
        {"phase": "llm.decode_step", "t": 102.1, "dur_s": 0.03, "extra": step},
    ]
    requests = [{"prompt_tokens": 300, "tokens": [101.6 + 0.01 * k for k in range(40)]}]
    return {"spans": spans, "window": [90.0, 135.0], "trace": reduced, "peaks": peaks, "config": c,
            "requests": requests}


def test_the_four_readers_the_cell_brings():
    c, fam = _published(), harness.family(_published())
    rec = _records()
    peaks = rec["peaks"]
    read = lambda name, r=rec: harness.reader("layer_metrics", name)(r)  # noqa: E731
    # the state step: 60 sessions' states once in and once out over 18 ms a step
    value, unit = read("state_step_roofline_pct")
    assert unit == "%" and value == pytest.approx(
        100 * (2 * 60 * fam.state_step_bytes_per_slot(c) / peaks["hbm_bytes_per_s"]) / 0.018)
    assert 60 < value < 65
    assert read("decode_state_time_pct") == (pytest.approx(100 * 0.036 / 0.060), "%")
    # the attention kernel: the rows the traced tokens attended, at 8,192 B a position
    rows = sum(300 + k for k in range(40)) / 2
    least = flops_bytes.roofline_pct(*fam.attention_decode(c, rows), 1.0, peaks)[0] / 100
    assert read("attn_decode_kernel_roofline_pct.head64") == (pytest.approx(100 * least / 0.001), "%")
    ops, nbytes = fam.decode_step(c, 60, rows)
    share = flops_bytes.roofline_pct(ops, nbytes, 0.030, peaks)[0]
    assert read("pg_decode_roofline_pct.state") == (pytest.approx(share), "%") and 0 < share < 100
    assert harness.reader("layer_metrics", "decode_attn_time_pct")(rec) == (pytest.approx(100 * 0.002 / 0.06), "%")
    names = ("state_step_roofline_pct", "decode_state_time_pct", "attn_decode_kernel_roofline_pct.head64",
             "pg_decode_roofline_pct.state")
    for name in names:  # nothing to read: None, and no raise, as on the parent commit
        for without in ({"trace": None}, {"trace": {**rec["trace"], "t0_wall": None}},
                        {"trace": {**rec["trace"], "ops": [["fusion.9", 0.1]], "program_runs": []}}):
            assert read(name, {**rec, **without}) is None, (name, without)
    for name in names[:1] + names[2:]:
        assert read(name, {**rec, "peaks": None}) is None
    # a family with experts has its own decode reader; spans from before the family carry no state_slots
    assert read("pg_decode_roofline_pct.state", _records(experts_touched=7)) is None
    bare = _records()
    for s in bare["spans"]:
        s["extra"] = {"batch": 60}
    assert read("pg_decode_roofline_pct.state", bare) is None and read("state_step_roofline_pct", bare) is None


def test_the_cell_is_listed_where_its_readers_find_something():
    b = harness.benchmark()
    listed = {m["name"] for m in b["per_layer"] if CELL in m.get("workloads", ())}
    assert {"state_step_roofline_pct", "decode_state_time_pct", "pg_decode_roofline_pct.state",
            "attn_decode_kernel_roofline_pct.head64", "decode_attn_time_pct",
            "pg_prefill_roofline_pct.batch"} <= listed
    assert not listed & {"moe_experts_touched_pct", "moe_picks_here_pct", "pg_decode_roofline_pct.kimilinear",
                         "kv_pool_pad_pct"}
    for name in ("state_step_roofline_pct", "decode_state_time_pct", "pg_decode_roofline_pct.state",
                 "attn_decode_kernel_roofline_pct.head64"):
        (m,) = [m for m in b["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s" and m["unit"] == "%"
    assert CELL in next(m for m in b["end_to_end"] if m["name"] == "out_tok_s")["workloads"]
