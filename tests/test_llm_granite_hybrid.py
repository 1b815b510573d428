"""Granite 4.0-H (``granitemoehybrid``) through ``LLMEngine`` at a tiny size on
the CPU: the logits it samples from against the plain reference's full
forward; a request's greedy tokens whatever slot it gets, whatever ran there
before (the state from zero whatever it held) and whoever shares its steps; a
slot that is not live left bit for bit; chunked against whole prefill; what
the engine refuses for this family, by name; the fields its spans carry.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import granitemoehybrid_ref as ref  # noqa: E402
from ray_tpu.core.config import GLOBAL_CONFIG  # noqa: E402
from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.models import granite_hybrid as gh  # noqa: E402
from ray_tpu.util import flightrec  # noqa: E402
from test_granite_hybrid import ATOL, RTOL, ref_config  # noqa: E402

pytestmark = pytest.mark.timeout(300)


def llm_config(**kw):
    return LLMConfig(**{
        "model_config": gh.GraniteHybridConfig.tiny(max_seq=128), "max_slots": 3,
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
        np.testing.assert_allclose(got, want[len(p) - 1 : len(p) + 4], rtol=RTOL, atol=ATOL)


def test_greedy_tokens_do_not_depend_on_slot_history_or_company(engine):
    """Alone; in a full batch (three slots, five requests, so two of them
    start in a slot and in blocks another request has just left: the state
    from zero whatever it held); and alone again in a used engine."""
    ps = prompts(5, np.random.default_rng(2))
    alone = [generate(LLMEngine(llm_config()), p) for p in ps[:2]]
    resets = engine.stats["state_resets"]
    together = engine.generate(ps, SamplingParams(max_tokens=6))
    assert engine.stats["state_resets"] == resets + 5
    assert [o["token_ids"] for o in together[:2]] == alone
    for p, o in zip(ps, together):  # every slot has been used by now
        assert generate(engine, p) == o["token_ids"]


def test_a_finished_slots_state_stays_while_others_step():
    eng = LLMEngine(llm_config())
    short, long_ = prompts(2, np.random.default_rng(7))
    eng.add_request("short", short, SamplingParams(max_tokens=4, stop_token=-1))
    eng.add_request("long", long_, SamplingParams(max_tokens=9, stop_token=-1))
    eng.step()
    slot = eng.requests["short"].slot
    assert slot >= 0
    while not eng.requests["short"].finished:
        eng.step()
    for _ in range(2):  # a step that was in flight when it ended may still land
        eng.step()
    left = np.asarray(eng.pool["state"][:, slot])
    assert np.abs(left).max() > 0
    while eng.has_unfinished():
        eng.step()
    np.testing.assert_array_equal(eng.pool["state"][:, slot], left)


def test_chunked_prefill_is_whole_prefill():
    ps = prompts(3, np.random.default_rng(4), lo=70, hi=120)
    whole = LLMEngine(llm_config()).generate(ps, SamplingParams(max_tokens=6))
    eng = LLMEngine(llm_config(prefill_chunk_tokens=16))
    chunked = eng.generate(ps, SamplingParams(max_tokens=6))
    assert eng.stats["prefill_chunks"] >= 3 * 5
    assert [o["token_ids"] for o in chunked] == [o["token_ids"] for o in whole]


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
        assert "recurrent state" in str(e.value) and "granitemoehybrid" in str(e.value)
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
        assert eng.stats["prefix_cache_bypassed"] == 2 and eng.stats["prefix_hits"] == 0


def test_spans_and_counters_of_the_state_and_the_attention_layers(engine):
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
    for x in steps:
        assert x["state_slots"] == x["batch"] and x["state_layers"] == 6
        assert "experts_touched" not in x and "picks" not in x
    for x in fills:
        assert x["state_slots"] == 1 and x["state_layers"] == 6 and x["state_carried"] == 0
    for part in ("kv", "state", "conv"):
        assert engine.stats[f"cache_bytes_{part}"] == engine.pool[part].nbytes
    blocks = engine.pool["kv"].shape[1]
    needed = 2 * cfg.n_kv_head * 2 * cfg.head_dim * 4 * 16 * blocks  # attention layers x a position's bytes
    assert engine.stats["cache_bytes_needed_kv"] == needed == engine.stats["cache_bytes_kv"]
    assert engine.stats["decode_attn_gather_steps"] > 0 == engine.stats["decode_attn_kernel_steps"]
    assert engine.stats["state_plain_steps"] > 0 == engine.stats["state_kernel_steps"]
