"""Nemotron-H through ``LLMEngine``: keys and values in blocks and a state per
slot side by side under continuous batching, at a tiny size on the CPU. Logits
against the plain reference's full forward; a request's greedy tokens whatever
slot it gets, whatever ran there before and whoever shares its steps; chunked
against whole prefill; the step's operand at 64 slots; what the engine refuses
for this family, by name; and the fields its spans carry.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import nemotron_h_ref as ref  # noqa: E402
from ray_tpu.core.config import GLOBAL_CONFIG  # noqa: E402
from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.models import nemotron_h as nh  # noqa: E402
from ray_tpu.util import flightrec  # noqa: E402
from test_nemotron_h import ref_config  # noqa: E402

pytestmark = pytest.mark.timeout(300)


def llm_config(**kw):
    return LLMConfig(**{
        "model_config": nh.NemotronHConfig.tiny(max_seq=128), "max_slots": 3,
        "max_seq": 128, "prefill_buckets": (32, 64, 128), "kv_block_size": 16,
        "prefix_chunk": 16, "seed": 0, "enable_prefix_caching": False, **kw,
    })


def prompts(n, rng=None, lo=9, hi=60):
    rng = rng or np.random.default_rng(1)
    return [rng.integers(3, 500, size=int(rng.integers(lo, hi))).tolist() for _ in range(n)]


def generate(engine, prompt, max_tokens=6):
    return engine.generate([prompt], SamplingParams(max_tokens=max_tokens))[0]["token_ids"]


@pytest.fixture(scope="module")
def engine():
    return LLMEngine(llm_config())


def test_prefill_and_decode_through_the_engine_are_the_reference_forward(engine):
    """The logits the engine samples from, at every step of three requests
    that share their steps, are the reference's at those positions."""
    seen: dict = {}
    sample = engine._sample

    def recording(logits, req):
        seen.setdefault(req.request_id, []).append(np.array(logits))
        return sample(logits, req)

    engine._sample = recording
    try:
        ps = prompts(3)
        outs = engine.generate(ps, SamplingParams(max_tokens=5))
    finally:
        engine._sample = sample
    c = ref_config(engine.model_config)
    for p, out in zip(ps, outs):
        toks = p + out["token_ids"]
        want = ref.forward(engine.params, jnp.asarray([toks], jnp.int32), c)[0]
        got = np.stack(seen[out["request_id"]])
        assert got.shape == (5, engine.model_config.vocab_size)
        np.testing.assert_allclose(got, want[len(p) - 1 : len(p) + 4], rtol=2e-3, atol=2e-5)


def test_greedy_tokens_do_not_depend_on_slot_history_or_company(engine):
    """Alone; in a full batch (three slots, five requests, so two of them
    start in a slot and in blocks another request has just left); and alone
    again in a used engine: the same tokens."""
    ps = prompts(5, np.random.default_rng(2))
    alone = [generate(LLMEngine(llm_config()), p) for p in ps[:2]]
    resets = engine.stats["state_resets"]
    together = engine.generate(ps, SamplingParams(max_tokens=6))
    assert engine.stats["state_resets"] == resets + 5
    assert [o["token_ids"] for o in together[:2]] == alone
    for p, o in zip(ps, together):  # every slot has been used by now
        assert generate(engine, p) == o["token_ids"]


def test_a_freed_slots_stale_state_is_not_read():
    """A request in the slot a longer one just left gives what it gives in a
    fresh engine, although the state row still held the other's state."""
    eng = LLMEngine(llm_config(max_slots=1))
    a, b = prompts(2, np.random.default_rng(3))
    generate(eng, a, max_tokens=9)
    left = np.asarray(eng.pool["state"][:, 0])
    assert np.abs(left).max() > 0  # the row is not cleared on release ...
    assert generate(eng, b) == generate(LLMEngine(llm_config(max_slots=1)), b)  # ... but at prefill


def test_a_finished_slots_state_stays_while_others_step():
    """A slot that is not live is stepped by nobody: the state a finished
    request left is bit for bit what it was after the others' later steps."""
    eng = LLMEngine(llm_config())
    short, long_ = prompts(2, np.random.default_rng(7))
    eng.add_request("short", short, SamplingParams(max_tokens=2, stop_token=-1))
    eng.add_request("long", long_, SamplingParams(max_tokens=9, stop_token=-1))
    while not eng.requests["short"].finished:
        eng.step()
    slot = eng.requests["short"].slot
    for _ in range(2):  # a step that was in flight when it ended may still land
        eng.step()
    left = np.asarray(eng.pool["state"][:, slot])
    while eng.has_unfinished():
        eng.step()
    np.testing.assert_array_equal(eng.pool["state"][:, slot], left)


def test_chunked_prefill_is_whole_prefill():
    """16-token chunks carry state and convolution tail from chunk to chunk
    and attend the keys and values the earlier chunks wrote; the decode steps
    of other requests between the chunks leave them alone."""
    ps = prompts(3, np.random.default_rng(4), lo=70, hi=120)
    whole = LLMEngine(llm_config()).generate(ps, SamplingParams(max_tokens=6))
    eng = LLMEngine(llm_config(prefill_chunk_tokens=16))
    chunked = eng.generate(ps, SamplingParams(max_tokens=6))
    assert eng.stats["prefill_chunks"] >= 3 * 5
    assert [o["token_ids"] for o in chunked] == [o["token_ids"] for o in whole]


def test_padded_bucket_tails_leave_the_state_alone():
    """The same prompts through one wide bucket (every prompt padded to 128)
    and through the ladder."""
    ps = prompts(3, np.random.default_rng(5))
    ladder = LLMEngine(llm_config()).generate(ps, SamplingParams(max_tokens=6))
    wide = LLMEngine(llm_config(prefill_buckets=(128,))).generate(ps, SamplingParams(max_tokens=6))
    assert [o["token_ids"] for o in wide] == [o["token_ids"] for o in ladder]


def test_sixty_four_slots_ride_one_operand():
    """The cell's width: 64 slots, a state of 65 rows, and every small operand
    of a decode step in one ``[64, 4 + W]`` int32 array; seventy requests churn
    through the slots and each gets the tokens it gets alone."""
    eng = LLMEngine(llm_config(max_slots=64, max_seq=64, prefill_buckets=(16, 32), num_kv_blocks=129))
    assert eng.pool["state"].shape[1] == eng.pool["conv"].shape[1] == 65
    launched = []
    decode = eng._pg_decode

    def spy(params, prev, meta, pool):
        launched.append((tuple(meta.shape), np.dtype(meta.dtype)))  # also under eval_shape
        return decode(params, prev, meta, pool)

    eng._pg_decode = spy
    ps = prompts(70, np.random.default_rng(8), lo=5, hi=30)
    outs = eng.generate(ps, SamplingParams(max_tokens=4))
    assert set(launched) == {((64, 4 + 4), np.dtype("int32"))}
    assert eng.stats["state_resets"] == 70
    alone = LLMEngine(llm_config(max_slots=1, max_seq=64, prefill_buckets=(16, 32)))
    for i in (0, 17, 69):
        assert outs[i]["token_ids"] == generate(alone, ps[i], max_tokens=4)


@pytest.mark.parametrize("what, kw, match", [
    ("speculative verification", {"spec_decode_tokens": 2}, "spec_decode_tokens"),
    ("tensor parallelism", {"tensor_parallelism": 2}, "tensor_parallelism"),
    ("the disaggregated export", "prefill_only", "prefill_only"),
    ("the disaggregated import", "handoff", "handoff"),
    ("the prefix cache", "prefix", None),
])
def test_what_the_engine_cannot_do_for_this_family_is_said(engine, what, kw, match):
    if isinstance(kw, dict):
        with pytest.raises(ValueError, match=match) as e:
            LLMEngine(llm_config(**kw))
        assert "recurrent state" in str(e.value) and "nemotron_h" in str(e.value)
    elif kw == "prefill_only":
        with pytest.raises(ValueError, match=match) as e:
            engine.add_request("x", [1, 2, 3], prefill_only=True)
        assert "recurrent state" in str(e.value)
    elif kw == "handoff":
        with pytest.raises(ValueError, match=match) as e:
            engine.add_handoff_request("x", {"prompt": [1, 2, 3]})
        assert "recurrent state" in str(e.value)
    else:  # bypassed and counted, and a repeated prompt is still served right
        eng = LLMEngine(llm_config(enable_prefix_caching=True))
        (p,) = prompts(1, np.random.default_rng(6), lo=40, hi=41)
        first, again = generate(eng, p), generate(eng, p)
        assert first == again == generate(engine, p)
        assert eng.stats["prefix_cache_bypassed"] == 2
        assert eng.stats["prefix_hits"] == eng.stats["prefix_lookups"] == 0
        assert eng.stats["prefill_tokens"] == 2 * len(p)


def test_spans_and_counters_of_the_experts_the_state_and_the_attention_block(engine):
    saved = GLOBAL_CONFIG.flightrec
    GLOBAL_CONFIG.flightrec = True
    flightrec.reset()
    try:
        engine.generate(prompts(2), SamplingParams(max_tokens=4))
        events = [e for r in flightrec.snapshot(planes=("llm",))["rings"].values()
                  for e in r["events"]]
    finally:
        GLOBAL_CONFIG.flightrec = saved
        flightrec.reset()
    cfg = engine.model_config
    steps = [e["extra"] for e in events if e["phase"] == "llm.decode_step"]
    fills = [e["extra"] for e in events if e["phase"] == "llm.prefill"]
    assert steps and len(fills) == 2
    table_rows = engine.block_tables.size * 16
    for x in steps:
        assert x["state_slots"] == x["batch"]
        assert x["picks"] == x["batch"] * cfg.experts_per_token * cfg.n_moe_layers
        assert x["picks_here"] == x["picks"]  # all eight experts are held
        assert 0 < x["experts_touched"] <= x["experts_held"] == 8 * cfg.n_moe_layers
        assert x["batch"] <= x["kv_blocks_live"] * 16 <= table_rows  # the attention block's rows
        assert "latent_rows_read" not in x  # no latent rows in this pool
    for x in fills:
        assert x["state_slots"] == 1 and x["picks_here"] == x["picks"]
        assert x["picks"] == x["tokens"] * cfg.experts_per_token * cfg.n_moe_layers
        assert x["tokens"] <= x["bucket"]  # the scans ran ceil(bucket / 128) chunks an M block
    for part in ("k", "v", "state", "conv"):
        assert engine.stats[f"cache_bytes_{part}"] == engine.pool[part].nbytes
    assert engine.stats["cache_bytes_state"] == (
        cfg.held.count("M") * (3 + 1) * cfg.mamba_heads * cfg.mamba_head_dim * cfg.ssm_state * 4
    )
    assert engine.stats["decode_attn_gather_steps"] > 0 == engine.stats["decode_attn_kernel_steps"]
