"""The ``mla_moe`` family's files (PR 33): its output check at a size a test
run can hold (the program, driven through the engine, passes; the reference in
fp8, without rotation and without YaRN, and wronged block tables do not), the
configuration against the catalog, its operation and byte counts against
numbers reckoned by hand, and the reader of the latent rows' counters."""

import json
import os

import pytest

from benchmarks import check, flops_bytes, harness

CELL = "serve-reason-axk1"


def _tiny():
    return harness.cell_files(harness.cell(CELL), rehearsal=1)


def _published():
    return harness.config_of(harness.cell(CELL))


@pytest.mark.parametrize("seed", [1, 3000000011])
def test_program_agrees_and_the_controls_do_not(seed):
    """bf16 weights and activations at the tiny widths: the program reads
    0.004 on both numbers. Every control is outside at least one of them by
    five times or more: fp8 by both; a reference that leaves the rotation out,
    or rotates by the plain frequencies, by the rows (the logits hardly tell at
    this size: 8 rope dims of 24 under near-uniform attention; PERF.md section
    2 has the chip's readings); a wronged table by both, the rows most."""
    c, mix = _tiny()
    program = check.check_one(c, mix, seed, "program")
    assert program["logits_rel_err"] < 0.02 and program["latent_rel_err"] < 0.02
    assert program["route_agree_pct"] > 95.0
    for who, logits, latent in (
        ("fp8", 5, 5), ("norope", None, 5), ("noyarn", None, 5),
        ("displaced", 5, 50), ("swapped_tables", 5, 50),
    ):
        wrong = check.check_one(c, mix, seed, who)
        assert logits is None or wrong["logits_rel_err"] > logits * program["logits_rel_err"], who
        assert wrong["latent_rel_err"] > latent * program["latent_rel_err"], who
    # bf16 is no control for a bf16 model: the reference in bf16 is closer than the program
    assert check.check_one(c, mix, seed, "bf16")["logits_rel_err"] < program["logits_rel_err"]
    with pytest.raises(SystemExit, match="unknown --who"):
        check.check_one(c, mix, seed, "int4")


def test_the_configuration_is_the_published_one_cut_as_it_says():
    c = _published()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["source_url"] == c["source"])
        differ = sorted(k for k, v in row["config"].items() if c.get(k) != v)
        assert differ == sorted(c["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
        assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    mix = harness.traffic_of(harness.cell(CELL))
    cfg = harness.family(c).model_config(c, mix)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset) == (192, 12, 0)
    assert (cfg.n_group, cfg.topk_group, cfg.experts_per_token) == (8, 4, 8)
    assert (cfg.d_model, cfg.n_head, cfg.q_lora_rank, cfg.latent_dim) == (7168, 64, 1536, 576)
    assert (cfg.d_ff, cfg.moe_d_ff, cfg.n_layer, cfg.max_seq) == (18432, 2048, 7, 4096)
    assert cfg.softmax_scale == pytest.approx(192**-0.5 * 1.34657**2, rel=1e-4)
    for key in ("topk_method", "group_score", "router", "weights", "tokenizer"):
        assert key in c["assumed"], key
    for said in ("16 chips", "over 8 chips", "9 stages"):
        assert said in c["deployment"], said
    # the floors a cut keeps: the dense layer and four more, 8 experts, 1/8 of the vocabulary
    assert c["num_hidden_layers"] >= 1 + 4 and c["n_routed_experts"] >= 8
    assert c["vocab_size"] * 8 >= c["published"]["vocab_size"]
    # the mix holds 32 slots of 4,096 positions and the scratch block
    e = mix["engine"]
    assert e["num_kv_blocks"] == e["max_slots"] * e["max_seq"] // e["kv_block_size"] + 1 == 8193
    assert max(mix["prompt_tokens"]) + max(mix["output_tokens"]) < e["max_seq"]
    assert mix["clients"] == e["max_slots"] == 32


def test_weight_and_cache_bytes_by_hand():
    """ISSUE 33's arithmetic, in this repo's bytes (bf16 2 B, routers float32)."""
    c, fam = _published(), harness.family(_published())
    D, V = 7168, 20480
    mla = D * 1536 + 1536 * 64 * 192 + D * 576 + 512 * 64 * 256 + 8192 * D  # 101.1 M in matrices
    assert mla == 101_122_048
    expert = 3 * D * 2048  # 44.04 M
    non_expert = 2 * (
        7 * (mla + 512 + 1536 + 2 * D) + 3 * D * 18432 + 6 * expert + D + D * V
    ) + 6 * D * 192 * 4
    assert fam.non_expert_weight_bytes(c) == non_expert == 3_063_789_568
    assert fam.weight_bytes(c) == non_expert + 6 * 12 * expert * 2 == 9_405_577_216
    assert fam.kv_bytes_per_token(c) == 7 * 576 * 2 == 8064  # 8.1 KB a token over 7 layers
    # the parameters the program draws: the counts above and the embedding table
    import jax

    mix = harness.traffic_of(harness.cell(CELL))
    cfg = fam.model_config(c, mix)
    shapes = jax.eval_shape(lambda k: fam.init_params(k, cfg), jax.random.key(0))
    drawn = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert drawn == fam.weight_bytes(c) + V * D * 2 == 9_699_178_496  # 9.70 GB
    from ray_tpu.models import paged

    e = mix["engine"]
    pool = jax.eval_shape(lambda: paged.init_block_pool(cfg, e["num_kv_blocks"], e["kv_block_size"]))
    # rows of 576 values held in whole 128-lane tiles (640): 1.17 GB where the values alone are 1.06
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool)) == 7 * 8193 * 16 * 640 * 2 == 1_174_548_480


def test_decode_step_and_prefill_counts_by_hand():
    c, fam = _published(), harness.family(_published())
    peak = harness.peaks_for("TPU v5 lite")
    assert fam.experts_touched(c, 1) == pytest.approx(0.5)  # 8 picks, a sixteenth of them here
    assert fam.experts_touched(c, 32) == pytest.approx(12 * (1 - (23 / 24) ** 32))  # about 9
    assert 8.8 < fam.experts_touched(c, 32) < 9.0
    batch, context = 32, 32 * 2500
    ops, nbytes = fam.decode_step(c, batch, context)
    touched = 6 * 12 * (1 - (23 / 24) ** 32)
    expert = 3 * 7168 * 2048
    assert nbytes == pytest.approx(
        3_063_789_568 + touched * expert * 2 + 8064 * (context + batch)
    )
    # the reader of the roofline share hands over what the program counted
    assert fam.decode_step(c, batch, context, touched=touched) == (ops, nbytes)
    assert fam.decode_step(c, batch, context, touched=0)[1] == pytest.approx(nbytes - touched * expert * 2)
    D = 7168
    per_token = 2 * (
        7 * 101_122_048 + 3 * D * 18432
        + 6 * (D * 192 + expert + 0.5 * expert)  # router, shared, half a pick here
    )
    attn = 2 * 7 * 64 * (2 * 512 + 64) * context  # 139 kFLOP a cached token and layer
    assert 2 * 64 * (2 * 512 + 64) == 139_264
    assert ops == pytest.approx(batch * (per_token + 2 * D * 20480) + attn)
    # bound by bytes: the step's bytes take longer than its operations at the chip's peaks
    share, bound = flops_bytes.roofline_pct(ops, nbytes, 0.020, peak)
    assert bound == "memory" and 40 < share < 60  # 8.3 GB: 10.1 ms at the chip's bandwidth
    T = 2048
    ops, nbytes = fam.prefill(c, T)
    assert ops == pytest.approx(
        T * per_token + 2 * D * 20480 + 2 * 7 * 64 * (192 + 128) * T * (T + 1) / 2
    )
    assert nbytes == fam.weight_bytes(c) + 8064 * T
    assert flops_bytes.roofline_pct(ops, nbytes, 0.1, peak)[1] == "compute"


def _step(t, batch, live, read):
    return {"phase": "llm.decode_step", "t": t, "dur_s": 0.015,
            "extra": {"batch": batch, "latent_rows_live": live, "latent_rows_read": read}}


def test_the_live_row_share_reads_its_fields_and_nothing_before_they_existed():
    read = harness.reader("layer_metrics", "latent_live_row_pct")
    table = 32 * 4096
    spans = [_step(99.0, 32, 50_000, table), _step(101.6, 32, 80_000, table), _step(102.7, 31, 70_000, table)]
    rec = {"spans": spans, "window": [100.0, 104.0], "trace": None, "peaks": None}
    assert read(rec) == (pytest.approx(100.0 * 150_000 / (2 * table)), "%")  # the steps inside the window
    traced = {"t0_wall": 101.5, "window_s": 1.0}  # a traced run reads the traced seconds only
    assert read({**rec, "trace": traced}) == (pytest.approx(100.0 * 80_000 / table), "%")
    before = [{**s, "extra": {"batch": s["extra"]["batch"]}} for s in spans]
    assert read({**rec, "spans": before}) is None
    assert read({**rec, "spans": []}) is None
