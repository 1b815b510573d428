"""The ``deepseek_v32`` family through ``LLMEngine``: a latent pool and an
index-key pool under one block table, no state per slot, under continuous
batching, at a tiny size on the CPU with ``index_topk`` 16 of up to 120
positions, so that every later chunk and every decode step selects. Logits
against the plain reference's full forward; the prefix cache and chunked
prefill over both pool parts; what the engine refuses for it; its spans.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import deepseek_v32_ref as ref  # noqa: E402
from ray_tpu.core.config import GLOBAL_CONFIG  # noqa: E402
from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.models import deepseek_v32 as dv  # noqa: E402
from ray_tpu.util import flightrec  # noqa: E402
from test_deepseek_v32 import ref_config  # noqa: E402
from test_llm_mla_moe import prompts, recorded  # noqa: E402

pytestmark = pytest.mark.timeout(300)


def llm_config(**kw):
    return LLMConfig(**{
        "model_config": dv.DeepseekV32Config.tiny(max_seq=128), "max_slots": 3,
        "max_seq": 128, "prefill_buckets": (32, 64, 128), "kv_block_size": 16,
        "prefix_chunk": 16, "seed": 0, **kw,
    })


@pytest.fixture(scope="module")
def engine():
    return LLMEngine(llm_config())


@pytest.mark.parametrize("chunk", [0, 32], ids=["whole", "chunks_of_32"])
def test_prefill_and_decode_through_the_engine_are_the_reference_forward(chunk):
    """The logits the engine samples from, at every step of four requests over
    three slots (so one starts in blocks another has just left), are the
    reference's at those positions, prompts prefilled whole or in chunks of 32
    whose queries score the index keys the chunks before left in the pool.
    float32 on both sides: 2e-4."""
    eng = LLMEngine(llm_config(prefill_chunk_tokens=chunk))
    ps = prompts(4, lo=40, hi=110)
    outs, logits = recorded(eng, ps)
    c = ref_config(eng.model_config)
    for p, out, got in zip(ps, outs, logits):
        toks = p + out["token_ids"]
        want = ref.forward(eng.params, jnp.asarray(toks, jnp.int32), c)
        assert got.shape == (5, eng.model_config.vocab_size)
        np.testing.assert_allclose(got, want[len(p) - 1 : len(p) + 4], rtol=2e-4, atol=2e-6)
    assert (eng.stats["prefill_chunks"] > 0) == bool(chunk)
    assert "state_resets" not in eng.stats and set(eng.pool) == {"ckv", "ikv"}
    # rows chosen one by one: neither arm of the latent kernel, and the stats say the gather
    assert eng.stats["decode_attn_kernel_steps"] == 0 < eng.stats["decode_attn_gather_steps"]


def test_a_repeated_prompt_hits_the_prefix_cache_which_serves_both_pool_parts():
    """The second request points its table at the first one's blocks and
    prefills the remainder from ``start`` 48: its queries score the *index
    keys* of the shared blocks and attend the *latent rows* they select there,
    and sample from the same logits as the miss. Were either part not shared
    by block id, the hit's selection or its attention would read zeros."""
    eng = LLMEngine(llm_config())
    (p,) = prompts(1, np.random.default_rng(6), lo=50, hi=51)
    (miss,), (miss_logits,) = recorded(eng, [p])
    assert eng.stats["prefix_hits"] == 0 and eng.stats["prefill_tokens"] == len(p)
    (hit,), (hit_logits,) = recorded(eng, [p])
    assert eng.stats["prefix_hits"] == 1 and eng.stats["prefix_tokens_reused"] == 48
    assert eng.stats["prefill_tokens"] == len(p) + 2  # the two tokens behind the shared 48
    assert hit["token_ids"] == miss["token_ids"]
    np.testing.assert_allclose(hit_logits, miss_logits, rtol=2e-4, atol=2e-6)
    # another prompt behind the same 48 tokens: the shared rows and keys under its own tail
    other = p[:48] + prompts(1, np.random.default_rng(7), lo=40, hi=41)[0]
    alone = LLMEngine(llm_config(enable_prefix_caching=False))
    (_,), (want,) = recorded(alone, [other])
    (_,), (got,) = recorded(eng, [other])
    assert eng.stats["prefix_hits"] == 2 and eng.stats["prefix_tokens_reused"] == 48 + 48
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    # and the reference agrees: 88 positions of which each later query keeps 16
    toks = jnp.asarray(other, jnp.int32)
    np.testing.assert_allclose(
        got[0], ref.forward(eng.params, toks, ref_config(eng.model_config))[len(other) - 1], rtol=2e-4, atol=2e-6
    )


def test_greedy_tokens_do_not_depend_on_slot_history_or_company(engine):
    ps = prompts(5, np.random.default_rng(2))
    alone = [
        LLMEngine(llm_config()).generate([p], SamplingParams(max_tokens=6))[0]["token_ids"]
        for p in ps[:2]
    ]
    together = engine.generate(ps, SamplingParams(max_tokens=6))
    assert [o["token_ids"] for o in together[:2]] == alone


@pytest.mark.parametrize("what, kw, match", [
    ("speculative verification", {"spec_decode_tokens": 2}, "spec_decode_tokens"),
    ("tensor parallelism", {"tensor_parallelism": 2}, "tensor_parallelism"),
    ("the disaggregated export", "prefill_only", "prefill_only"),
])
def test_what_the_engine_cannot_do_for_this_family_is_said_with_its_own_reason(engine, what, kw, match):
    if isinstance(kw, dict):
        with pytest.raises(ValueError, match=match) as e:
            LLMEngine(llm_config(**kw))
    else:
        with pytest.raises(ValueError, match=match) as e:
            engine.add_request("x", [1, 2, 3], prefill_only=True)
    assert "'deepseek_v32' brings its own paged programs over latent rows" in str(e.value)


def test_spans_carry_the_selections_counters_beside_the_experts(engine):
    saved = GLOBAL_CONFIG.flightrec
    GLOBAL_CONFIG.flightrec = True
    flightrec.reset()
    try:
        ps = prompts(2, lo=30, hi=60)
        engine.generate(ps, SamplingParams(max_tokens=4))
        events = [e for r in flightrec.snapshot(planes=("llm",))["rings"].values()
                  for e in r["events"]]
    finally:
        GLOBAL_CONFIG.flightrec = saved
        flightrec.reset()
    cfg = engine.model_config
    steps = [e["extra"] for e in events if e["phase"] == "llm.decode_step"]
    fills = [e["extra"] for e in events if e["phase"] == "llm.prefill"]
    assert len(steps) == 3 and len(fills) == 2
    lens = sorted(len(p) for p in ps)
    for k, x in enumerate(steps):
        assert x["picks"] == x["batch"] * cfg.experts_per_token * cfg.n_moe_layers
        assert x["index_rows_scored"] == x["latent_rows_live"] == sum(n + k + 1 for n in lens)
        assert x["latent_rows_selected"] == 2 * cfg.index_topk  # both contexts are past 16
        assert x["latent_rows_read"] == 2 * cfg.index_topk  # the places each live slot's gather fills
    for x in fills:
        n = x["tokens"]
        assert x["picks_here"] == x["picks"] == n * cfg.experts_per_token * cfg.n_moe_layers
        assert x["index_pairs_scored"] == n * (n + 1) // 2
        assert x["latent_rows_selected"] == sum(min(t + 1, cfg.index_topk) for t in range(n))
        assert "latent_rows_read" not in x and "index_rows_scored" not in x
    assert engine.stats["cache_bytes_ikv"] == engine.pool["ikv"].nbytes
    assert engine.stats["cache_bytes_ckv"] == engine.pool["ckv"].nbytes == 8 * engine.pool["ikv"].nbytes
