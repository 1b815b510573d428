"""The ``deepseek_v32`` family's file: the output check at a rehearsal's
sizes on the CPU (``index_topk`` 16 of up to 127 positions: selection at
work), the configuration against the catalog's published keys, and the
operation and byte counts by hand."""

import json
import os

import pytest

from benchmarks import harness

FAMILY = harness._module("families", "deepseek_v32")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _published():
    return harness.load_json(harness.HERE, "configs", "deepseek-v3.2-exp.json")


def _tiny():
    return harness.shrink_for_rehearsal(_published(), harness.load_json(harness.HERE, "traffic", "longctx-backlog.json"))


@pytest.mark.parametrize("seed", [1, 3000000011])
def test_program_agrees_and_the_controls_do_not(seed):
    """At a rehearsal's sizes in bfloat16: the program is close to the
    reference on every compared number and agrees on nearly every selected
    position; the reference that attends everything (``dense``) or the newest
    positions (``recent``) disagrees on most of them and gives other logits;
    a wronged block table shows in both parts of the pool."""
    c, t = _tiny()
    got = {who: FAMILY.check(c, t, seed, who) for who in ("program", "dense", "recent", "displaced", "swapped_tables")}
    program = got["program"]
    assert set(program) == {
        "logits_rel_err", "latent_rel_err", "index_key_rel_err", "select_agree_pct", "select_miss_pct",
        "route_agree_pct",
    }
    assert program["logits_rel_err"] < 0.05 and program["latent_rel_err"] < 0.02
    assert program["index_key_rel_err"] < 0.02 and program["select_miss_pct"] < 5 and program["route_agree_pct"] > 95
    for who in ("dense", "recent"):
        assert got[who]["select_miss_pct"] > 50 and got[who]["logits_rel_err"] > 2 * program["logits_rel_err"]
    for who in ("displaced", "swapped_tables"):  # one table serves both parts: both are wronged
        assert got[who]["latent_rel_err"] > 0.2 and got[who]["index_key_rel_err"] > 0.2
    with pytest.raises(SystemExit, match="unknown --who"):
        FAMILY.check(c, t, seed, "norope")


def test_the_configuration_is_the_published_one_cut_as_it_says():
    c = _published()
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        (row,) = [r for r in rows if r["name"] == "DeepSeek-V3.2-Exp"]
        assert c["source"] == row["source_url"]
        differ = sorted(k for k, v in row["config"].items() if c.get(k) != v)
        assert differ == sorted(c["reduced"])
        assert {k: row["config"][k] for k in c["reduced"]} == c["published"]
    assert c["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["first_k_dense_replace"], c["n_routed_experts"], c["vocab_size"]) == (6, 1, 8, 16160)
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert {"index_rope", "index_dtype", "router", "mtp", "weights", "tokenizer", "index_norm_eps", "vocab_size"} <= set(c["assumed"])
    traffic = harness.load_json(harness.HERE, "traffic", "longctx-backlog.json")
    cfg = FAMILY.model_config(c, traffic)
    assert (cfg.n_layer, cfg.first_k_dense, cfg.n_experts, cfg.experts_held, cfg.n_head) == (6, 1, 256, 8, 128)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk, cfg.rope_factor) == (64, 128, 2048, 40.0)
    assert cfg.max_seq == 34816 and cfg.family == "deepseek_v32"
    prompts = traffic["prompt_tokens"]
    assert len(prompts) == 16 and sum(prompts) == 16 * 16128 and sorted(prompts)[8] == 14336 and max(prompts) == 32768  # ISSUE 56's first table
    assert all(p % traffic["engine"]["prefill_chunk_tokens"] == 0 for p in prompts)
    assert traffic["engine"]["num_kv_blocks"] == 16 * (34816 // 16) + 1


def test_weight_and_cache_bytes_by_hand():
    """ISSUE 56's arithmetic: MLA 187.1 M a layer, the indexer 14.0 M, a
    routed or shared expert 44.0 M; 3.825 B parameters with the embedding."""
    c = _published()
    mla = 7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256 + 16384 * 7168
    index = 1536 * 8192 + 7168 * 128 + 7168 * 64
    other = 512 + 1536 + 2 * 128 + 2 * 7168
    expert = 3 * 7168 * 2048
    non_expert = 6 * (mla + index + other) + 3 * 7168 * 18432 + 5 * expert + 7168 + 7168 * 16160
    assert FAMILY.non_expert_weight_bytes(c) == 2 * non_expert + 5 * (7168 * 256 + 256) * 4
    assert FAMILY.weight_bytes(c) == FAMILY.non_expert_weight_bytes(c) + 5 * 8 * expert * 2
    # with the embedding table, what init_params draws: 7,669,372,928 B (aot_rehearsal.py)
    assert FAMILY.weight_bytes(c) + 16160 * 7168 * 2 == 7_669_372_928
    assert FAMILY.kv_bytes_per_token(c) == 6 * (1152 + 256)


def test_decode_step_and_prefill_count_the_selection_not_the_context():
    c = _published()
    s = FAMILY._sizes(c)
    batch, context = 16, 16 * 16384
    ops, nbytes = FAMILY.decode_step(c, batch, context, touched=40)
    attn = 2 * 6 * 128 * (2 * 512 + 64) * batch * 2048  # over index_topk rows a sequence, whatever the context
    index = 6 * 2 * 64 * 129 * context
    matmul = batch * (FAMILY._token_matmul_ops(c) + 2 * 7168 * 16160)
    assert ops == matmul + index + attn
    assert nbytes == (
        FAMILY.non_expert_weight_bytes(c) + 40 * s["expert_mm"] * 2
        + 6 * 2 * (128 * context + 576 * batch * 2048) + 6 * 1408 * batch
    )
    # twice the context: the index scores double, attention stands still
    more, _ = FAMILY.decode_step(c, batch, 2 * context, touched=40)
    assert more - ops == index
    # a context within index_topk: every row is attended
    short, _ = FAMILY.decode_step(c, batch, 16 * 100, touched=40)
    assert short == matmul + 6 * 2 * 64 * 129 * 1600 + 2 * 6 * 128 * 1088 * 1600
    # a chunk of 2,048 from 14,336: every query keeps 2,048 rows and scores its own context
    ops, nbytes = FAMILY.prefill(c, 2048, touched=40, start=14336)
    pairs = sum(range(14337, 16385))
    assert FAMILY._pairs(14336, 2048) == pairs and FAMILY._pairs(14336, 2048, 2048) == 2048 * 2048
    assert FAMILY._pairs(0, 2048, 2048) == 2048 * 2049 // 2 and FAMILY._pairs(1024, 2048, 2048) == sum(range(1025, 2049)) + 1024 * 2048
    assert ops == (
        2048 * FAMILY._token_matmul_ops(c) + 2 * 7168 * 16160
        + 6 * 2 * 64 * 129 * pairs + 2 * 6 * 128 * 320 * 2048 * 2048
    )
    assert nbytes == (
        FAMILY.non_expert_weight_bytes(c) + 40 * s["expert_mm"] * 2 + 6 * 1408 * 2048
        + 6 * 2 * (128 * 14336 + 576 * 14336)
    )


def _step(t, batch, live, selected):
    return {"phase": "llm.decode_step", "t": t, "dur_s": 0.01,
            "extra": {"batch": batch, "latent_rows_live": live, "latent_rows_selected": selected}}


def test_the_selected_row_share_reads_its_fields_and_nothing_before_they_existed():
    read = harness.reader("layer_metrics", "latent_selected_row_pct")
    spans = [_step(1.0, 16, 16 * 16384, 16 * 2048), _step(2.0, 16, 16 * 8192, 16 * 2048)]
    records = {"spans": spans, "window": [0.0, 10.0], "trace": None, "requests": []}
    value, unit = read(records)
    assert unit == "%" and value == pytest.approx(100 * 2 * 2048 / (16384 + 8192))
    old = [{**s, "extra": {"batch": 16, "latent_rows_live": 5, "latent_rows_read": 9}} for s in spans]
    assert read({**records, "spans": old}) is None


def test_the_kernels_share_counts_selected_pairs_and_reads_nothing_without_the_kernel():
    c = _published()
    ops, nbytes = FAMILY.selected_attention(c, 2048, 14336)
    assert ops == 2 * 6 * 128 * 320 * 2048 * 2048
    assert nbytes == 6 * 2 * (2048 * 128 * 320 + 16384 * 576)
    first, _ = FAMILY.selected_attention(c, 2048, 0)
    assert first == 2 * 6 * 128 * 320 * (2048 * 2049 // 2)
    read = harness.reader("layer_metrics", "selected_attention_fold_roofline_pct")
    span = {"phase": "llm.prefill_chunk", "t": 1.0, "dur_s": 0.4, "extra": {"tokens": 2048, "start": 14336}}
    trace = {"t0_wall": 0.0, "window_s": 4.0, "program_runs": [["jit_paged_prefill(1)", 0, 5 * 10**8]],
             "ops": [["selected_attention_fold.3", 0.2], ["selected_attention_fold.4", 0.1], ["fusion.1", 0.1]]}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    records = {"config": c, "trace": trace, "spans": [span], "peaks": peaks}
    value, unit = read(records)
    assert unit == "%" and value == pytest.approx(100 * (ops / 197e12) / 0.3)
    assert read({**records, "trace": {**trace, "ops": [["fusion.1", 0.1]]}}) is None  # the fold, or the parent
    assert read({**records, "peaks": None}) is None and read({**records, "trace": None}) is None
