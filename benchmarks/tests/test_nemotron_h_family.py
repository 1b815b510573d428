"""The ``nemotron_h`` family's files (PR 35): its output check at a size a test
run can hold (the program, driven through the engine, passes; the reference in
fp8, with the gated norm over all channels or the experts unsquared, wronged
block tables and exchanged states do not), the configuration against the
catalog, its operation and byte counts against ``init_params``' shapes to the
byte and against numbers reckoned by hand, and the reader of the prefill
programs' roofline share."""

import json
import os

import pytest

from benchmarks import check, flops_bytes, harness

CELL = "serve-chat-nemotron3super"


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
    assert got["logits_rel_err"] < 0.03
    assert got["state_rel_err"] < 0.02 and got["kv_rel_err"] < 0.02
    assert got["route_agree_pct"] > 90.0  # three picks of eight, bf16 weights: a near tie flips


@pytest.mark.parametrize("who, number, times", [
    ("fp8", "logits_rel_err", 3),
    ("ungrouped_norm", "logits_rel_err", 3),
    ("unsquared", "logits_rel_err", 3),
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


def test_the_configuration_is_the_published_one_cut_as_it_says():
    c = _published()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["source_url"] == c["source"])
        differ = sorted(k for k, v in row["config"].items() if c.get(k) != v)
        assert differ == sorted(c["reduced"]) == [
            "hybrid_override_pattern", "n_routed_experts", "num_hidden_layers", "vocab_size"]
        assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    mix = harness.traffic_of(harness.cell(CELL))
    cfg = harness.family(c).model_config(c, mix)
    assert cfg.held == c["hybrid_override_pattern"] == "MEMEMEM*EME"
    assert c["published"]["hybrid_override_pattern"].startswith(cfg.held)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset, cfg.experts_per_token) == (512, 128, 0, 22)
    assert (cfg.d_model, cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_groups, cfg.ssm_state) == (4096, 128, 64, 8, 128)
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.conv_kernel) == (32, 2, 128, 4)
    assert (cfg.moe_latent, cfg.moe_d_ff, cfg.shared_d_ff, cfg.routed_scaling) == (1024, 2688, 5376, 5.0)
    assert cfg.hidden_act == "relu2" and cfg.state_slots == 64 and cfg.max_seq == 2048
    # the floors a cut keeps: a whole period (5 M : 5 E : 1 *), 8 experts, 1/8 of the vocabulary
    assert (cfg.held.count("M"), cfg.held.count("E"), cfg.held.count("*")) == (5, 5, 1)
    assert c["n_routed_experts"] >= 8 and c["vocab_size"] * 8 >= c["published"]["vocab_size"]
    # the mix is the issue's, letter for letter
    assert mix["clients"] == mix["engine"]["max_slots"] == 64 and mix["kind"] == "closed-loop"
    assert mix["prompt_tokens"] == [64, 96, 128, 160, 192, 224, 256, 256, 320, 384, 448, 512, 576, 640, 704, 768]
    assert mix["output_tokens"] == [64, 80, 96, 112, 128, 160, 176, 192, 192, 224, 256, 288, 320, 352, 368, 384]
    assert (mix["engine"]["max_seq"], mix["engine"]["kv_block_size"], mix["engine"]["num_kv_blocks"]) == (2048, 16, 8193)
    from ray_tpu.llm.config import LLMConfig

    assert tuple(mix["engine"]["prefill_buckets"]) == LLMConfig().prefill_buckets  # the default ladder


def test_weight_cache_and_state_bytes_by_hand_and_by_the_shapes_the_program_draws():
    """ISSUE 35's arithmetic, in this repo's bytes (bf16 2 B; router, the
    state-space scalars and the state float32)."""
    c, fam = _published(), harness.family(_published())
    D, V = 4096, 32768
    mamba = D * (8192 + 10240 + 128) + 8192 * D  # 109.6 M in matrices
    mamba_small = 5 * 10240 + 8192  # the convolution and its bias, the gated norm
    attention = 2 * D * 4096 + 2 * D * 256
    outside = 2 * D * 1024 + 2 * D * 5376  # the latent pair, the shared expert
    expert = 2 * 1024 * 2688  # 5.505 M
    router = (D * 512 + 512) * 4  # float32, with its bias
    non_expert = 2 * (
        5 * (mamba + mamba_small) + attention + 5 * outside + D * 11 + D + D * V
    ) + 5 * router + 5 * 3 * 128 * 4
    assert fam.non_expert_weight_bytes(c) == non_expert
    assert fam.weight_bytes(c) == non_expert + 5 * 128 * expert * 2  # + 7.05 GB of held experts
    assert fam.kv_bytes_per_token(c) == 2 * 2 * 128 * 2 == 1024  # one attention block: 1 KB a token
    assert fam.state_bytes_per_slot(c) == 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert 4.19e6 < 128 * 64 * 128 * 4 < 4.2e6  # 4.19 MB a slot a block
    # the parameters the program draws: the counts above and the embedding table, to the byte
    import jax

    from ray_tpu.models import paged

    mix = harness.traffic_of(harness.cell(CELL))
    cfg = fam.model_config(c, mix)
    shapes = jax.eval_shape(lambda k: fam.init_params(k, cfg), jax.random.key(0))
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))  # noqa: E731
    assert nbytes(shapes) == fam.weight_bytes(c) + V * D * 2
    assert round(sum(x.size for x in jax.tree.leaves(shapes)) / 1e6) == 4648
    assert nbytes(shapes) == 9_317_307_904  # 9.30 GB at 2 bytes a parameter, and the float32 routers
    # the pool the engine makes, against the same counts
    e = mix["engine"]
    pool = jax.eval_shape(lambda: paged.init_block_pool(cfg, e["num_kv_blocks"], e["kv_block_size"], e["max_slots"]))
    assert pool["state"].shape == (5, 65, 128, 64, 128) and pool["conv"].shape == (5, 65, 3, 10240)
    assert pool["k"].shape == pool["v"].shape == (1, 8193, 2, 16, 128)
    assert nbytes({k: pool[k] for k in ("state", "conv")}) == 65 * fam.state_bytes_per_slot(c)
    assert nbytes({k: pool[k] for k in ("k", "v")}) == 8193 * 16 * fam.kv_bytes_per_token(c)
    assert 1.38e9 < 65 * fam.state_bytes_per_slot(c) < 1.39e9


def test_decode_step_and_prefill_counts_by_hand():
    c, fam = _published(), harness.family(_published())
    assert fam.experts_touched(c, 1) == pytest.approx(5.5)  # 22 picks, a quarter of them here
    assert fam.experts_touched(c, 64) == pytest.approx(128 * (1 - (1 - 22 / 512) ** 64))
    assert 119 < fam.experts_touched(c, 64) < 121  # "about 120 of 128"
    expert_b, state_b = 2 * 1024 * 2688 * 2, 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    batch, context = 64, 64 * 400
    ops, nbytes = fam.decode_step(c, batch, context)
    touched = 5 * fam.experts_touched(c, 64)
    assert nbytes == pytest.approx(
        fam.non_expert_weight_bytes(c) + touched * expert_b + 2 * batch * state_b + 1024 * (context + batch)
    )
    assert 10.5e9 < nbytes < 12e9  # ISSUE 35: about 11.3 GB a step
    assert touched * expert_b == pytest.approx(6.6e9, rel=0.02) and 2 * batch * state_b == pytest.approx(2.7e9, rel=0.02)
    # the reader of the roofline share hands over what the program counted
    assert fam.decode_step(c, batch, context, touched=touched) == (ops, nbytes)
    assert fam.decode_step(c, batch, context, touched=0)[1] == pytest.approx(nbytes - touched * expert_b)
    D = 4096
    per_token = 2 * (
        5 * (D * 18560 + 8192 * D) + 2 * D * 4096 + 2 * D * 256
        + 5 * (D * 512 + 2 * D * 1024 + 2 * D * 5376 + 5.5 * 2 * 1024 * 2688)
    )
    recurrence = 5 * 128 * 5 * 64 * 128
    assert ops == pytest.approx(
        batch * (per_token + 2 * D * 32768 + recurrence) + 2 * 32 * 2 * 128 * context
    )
    assert nbytes / 819e9 > 5 * ops / 197e12  # memory-bound: the share is of bytes
    T = 512
    ops, nbytes = fam.prefill(c, T)
    assert ops == pytest.approx(
        T * (per_token + recurrence) + 2 * D * 32768 + 2 * 32 * 2 * 128 * T * (T + 1) / 2
    )
    assert nbytes == fam.weight_bytes(c) + 1024 * T + state_b
    assert fam.prefill(c, T, touched=600)[1] == nbytes - 40 * expert_b  # 600 of the 640 held reached


def test_the_prefill_roofline_reader_by_program_name_and_the_spans_tokens():
    read = harness.reader("layer_metrics", "pg_prefill_roofline_pct.batch")
    c, fam = _published(), harness.family(_published())
    peaks = harness.load_json(harness.HERE, "peaks.json")["TPU v5 lite"]
    ms = 1_000_000
    reduced = {"offset_ns": 0, "t0_wall": 101.5, "window_s": 1.0, "program_runs": [
        ["jit_paged_prefill(123)", 1000 * ms, 40 * ms],
        ["jit_paged_decode(77)", 1065 * ms, 8 * ms],  # no part of the prefill programs' time
        ["jit_paged_prefill(456)", 1080 * ms, 20 * ms],  # another bucket
    ]}
    spans = [
        {"phase": "llm.prefill", "t": 100.0, "dur_s": 0.05, "extra": {"tokens": 900, "bucket": 1024}},  # before the trace
        {"phase": "llm.prefill", "t": 101.6, "dur_s": 0.05, "extra": {"tokens": 300, "bucket": 512, "experts_touched": 630}},
        {"phase": "llm.prefill", "t": 102.1, "dur_s": 0.03, "extra": {"tokens": 100, "bucket": 128}},
        {"phase": "llm.decode_step", "t": 102.2, "dur_s": 0.01, "extra": {"batch": 9}},
    ]
    rec = {"spans": spans, "window": [90.0, 135.0], "trace": reduced, "peaks": peaks, "config": c}
    least = [
        flops_bytes.roofline_pct(*fam.prefill(c, 300, touched=630), 1.0, peaks)[0] / 100,
        flops_bytes.roofline_pct(*fam.prefill(c, 100), 1.0, peaks)[0] / 100,
    ]
    value, unit = read(rec)
    assert unit == "%" and value == pytest.approx(100.0 * (sum(least) / 2) / 0.030)
    assert 0 < value < 100
    for without in ({"peaks": None}, {"trace": None}, {"spans": spans[-1:]},
                    {"trace": {**reduced, "program_runs": reduced["program_runs"][1:2]}},
                    {"trace": {**reduced, "t0_wall": None}}):
        assert read({**rec, **without}) is None
    # a family whose prefill takes no counted experts is handed the tokens alone
    kimi = harness.config_of(harness.cell("serve-batch-kimilinear"))
    assert read({**rec, "config": kimi})[1] == "%"
