"""The ``mla_moe`` family through ``LLMEngine``: a latent block pool and no
state per slot under continuous batching, at a tiny size on the CPU. Logits
against the plain reference's full forward; the prefix cache and chunked
prefill over latent rows (which nothing in the repo ran before this family);
what the engine refuses for it, by name and for its own reason; its spans.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import mla_moe_ref as ref  # noqa: E402
from ray_tpu.core.config import GLOBAL_CONFIG  # noqa: E402
from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.models import mla_moe as mm, paged  # noqa: E402
from ray_tpu.util import flightrec  # noqa: E402
from test_mla_moe import ref_config  # noqa: E402

pytestmark = pytest.mark.timeout(300)


def llm_config(**kw):
    return LLMConfig(**{
        "model_config": mm.MlaMoeConfig.tiny(max_seq=128), "max_slots": 3,
        "max_seq": 128, "prefill_buckets": (32, 64, 128), "kv_block_size": 16,
        "prefix_chunk": 16, "seed": 0, **kw,
    })


def prompts(n, rng=None, lo=9, hi=60):
    rng = rng or np.random.default_rng(1)
    return [rng.integers(3, 500, size=int(rng.integers(lo, hi))).tolist() for _ in range(n)]


def recorded(engine, ps, max_tokens=5):
    """``generate`` with the logits the engine samples from noted by request."""
    seen: dict = {}
    sample = engine._sample

    def recording(logits, req):
        seen.setdefault(req.request_id, []).append(np.array(logits))
        return sample(logits, req)

    engine._sample = recording
    try:
        outs = engine.generate(ps, SamplingParams(max_tokens=max_tokens))
    finally:
        engine._sample = sample
    return outs, [np.stack(seen[o["request_id"]]) for o in outs]


@pytest.fixture(scope="module")
def engine():
    return LLMEngine(llm_config())


def test_prefill_and_decode_through_the_engine_are_the_reference_forward(engine):
    """The logits the engine samples from, at every step of four requests over
    three slots (so one starts in blocks another has just left), are the
    reference's at those positions. float32 on both sides: 2e-4."""
    ps = prompts(4)
    outs, logits = recorded(engine, ps)
    c = ref_config(engine.model_config)
    for p, out, got in zip(ps, outs, logits):
        toks = p + out["token_ids"]
        want = ref.forward(engine.params, jnp.asarray([toks], jnp.int32), c)[0]
        assert got.shape == (5, engine.model_config.vocab_size)
        np.testing.assert_allclose(got, want[len(p) - 1 : len(p) + 4], rtol=2e-4, atol=2e-6)
    assert "state_resets" not in engine.stats and "prefix_cache_bypassed" not in engine.stats
    assert set(engine.pool) == {"ckv"}


def test_a_repeated_prompt_hits_the_prefix_cache_and_its_logits_are_the_misses():
    """A latent pool under block tables is shared by prefix like keys and
    values: the second request points its table at the first one's blocks (no
    device copy), prefills the remainder from ``start`` = the shared length,
    and samples from the same logits. Rows hold their rotation by absolute
    position, so they are good for whoever reads them."""
    eng = LLMEngine(llm_config())
    (p,) = prompts(1, np.random.default_rng(6), lo=50, hi=51)
    (miss,), (miss_logits,) = recorded(eng, [p])
    assert eng.stats["prefix_hits"] == 0 and eng.stats["prefill_tokens"] == len(p)
    (hit,), (hit_logits,) = recorded(eng, [p])
    assert eng.stats["prefix_hits"] == 1 and eng.stats["prefix_tokens_reused"] == 48
    assert eng.stats["prefill_tokens"] == len(p) + 2  # the two tokens behind the shared 48
    assert hit["token_ids"] == miss["token_ids"]
    np.testing.assert_allclose(hit_logits, miss_logits, rtol=2e-4, atol=2e-6)
    # another prompt behind the same 48 tokens: the shared rows under its own tail
    other = p[:48] + prompts(1, np.random.default_rng(7), lo=20, hi=21)[0]
    alone = LLMEngine(llm_config(enable_prefix_caching=False))
    (_,), (want,) = recorded(alone, [other])
    (_,), (got,) = recorded(eng, [other])
    assert eng.stats["prefix_hits"] == 2 and eng.stats["prefix_tokens_reused"] == 48 + 48
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


def test_chunked_prefill_is_whole_prefill():
    """16-token chunks continue over the rows the chunks before left; the
    decode steps of other requests between the chunks write their garbage row
    where the next chunk begins, which overwrites it."""
    ps = prompts(3, np.random.default_rng(4), lo=70, hi=120)
    whole = LLMEngine(llm_config()).generate(ps, SamplingParams(max_tokens=6))
    eng = LLMEngine(llm_config(prefill_chunk_tokens=16))
    chunked = eng.generate(ps, SamplingParams(max_tokens=6))
    assert eng.stats["prefill_chunks"] >= 3 * 5
    assert [o["token_ids"] for o in chunked] == [o["token_ids"] for o in whole]


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
    ("the disaggregated import", "handoff", "handoff"),
])
def test_what_the_engine_cannot_do_for_this_family_is_said_with_its_own_reason(engine, what, kw, match):
    """Refused because the family brings its own programs over a cache that is
    not keys and values per head, not because of a state it does not keep."""
    if isinstance(kw, dict):
        with pytest.raises(ValueError, match=match) as e:
            LLMEngine(llm_config(**kw))
    elif kw == "prefill_only":
        with pytest.raises(ValueError, match=match) as e:
            engine.add_request("x", [1, 2, 3], prefill_only=True)
    else:
        with pytest.raises(ValueError, match=match) as e:
            engine.add_handoff_request("x", {"prompt": [1, 2, 3]})
    assert "'mla_moe' brings its own paged programs" in str(e.value)
    assert "recurrent state" not in str(e.value)


def test_spans_carry_the_expert_counters_and_the_latent_rows(engine):
    saved = GLOBAL_CONFIG.flightrec
    GLOBAL_CONFIG.flightrec = True
    flightrec.reset()
    try:
        ps = prompts(2)
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
        assert "state_slots" not in x
        assert x["picks"] == x["batch"] * cfg.experts_per_token * cfg.n_moe_layers
        assert x["picks_here"] == x["picks"]  # all eight experts are held
        assert 0 < x["experts_touched"] <= x["experts_held"] == 8 * cfg.n_moe_layers
        # every slot's whole table is gathered; the live rows are each request's positions so far
        assert x["latent_rows_read"] == 3 * 128
        assert x["latent_rows_live"] == sum(n + k + 1 for n in lens)
    for x in fills:
        assert x["picks_here"] == x["picks"] == x["tokens"] * cfg.experts_per_token * cfg.n_moe_layers
        assert "latent_rows_read" not in x
    assert engine.stats["cache_bytes_ckv"] == engine.pool["ckv"].nbytes


# -- decode over the live blocks in place (the kernel, interpreted here) ----------


def test_decode_through_the_kernel_is_decode_through_the_gather(engine):
    """``paged_decode(..., interpret=True)`` attends each slot's live blocks
    with the latent kernel in the Pallas interpreter; without it, here on the
    CPU, it gathers every table whole. Three slots at unlike positions (one on
    a block's last row, one a free slot on the scratch block) over a pool of
    stale values: the same logits and the same pool, to a float32 sum's
    tolerance."""
    cfg, bs = engine.model_config, 16
    pool = mm.init_pool(cfg, 25, bs)
    pool = {"ckv": jax.random.normal(jax.random.key(4), pool["ckv"].shape).at[..., cfg.latent_dim:].set(0)}
    tables = jnp.asarray(
        np.stack([np.random.default_rng(5).permutation(np.arange(1, 25))[:8] for _ in range(2)] + [np.zeros(8)]),
        jnp.int32,
    )
    positions = jnp.asarray([77, 31, 0], jnp.int32)
    tokens = jnp.asarray([5, 9, 0], jnp.int32)
    live = jnp.asarray([True, True, False])
    out = {
        interpret: jax.jit(functools.partial(mm.paged_decode, cfg=cfg, block_size=bs, interpret=interpret))(
            engine.params, tokens, positions, tables, pool, live=live
        )
        for interpret in (False, True)
    }
    (pool_g, logits_g, counts_g), (pool_k, logits_k, counts_k) = out[False], out[True]
    np.testing.assert_allclose(logits_k[:2], logits_g[:2], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(pool_k["ckv"], pool_g["ckv"], rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(counts_k, counts_g)
    # the step's own rows were written where the tables point, and are attended
    assert not np.allclose(pool_g["ckv"][:, tables[0, 4], 13], pool["ckv"][:, tables[0, 4], 13])


def test_an_engine_built_with_the_kernel_streams_the_gathers_tokens_and_says_so(monkeypatch):
    """The engine as a TPU replica has it (the arm named by
    ``paged.decode_attends_in_place``, the kernel run by the interpreter here):
    the same greedy tokens as the engine that gathers, every decode program
    counted under ``decode_attn_kernel_steps``, and ``latent_rows_read`` on a
    step's span the live slots' live blocks, not the tables."""
    ps = prompts(4, np.random.default_rng(8))
    gathered = LLMEngine(llm_config())
    want = gathered.generate(ps, SamplingParams(max_tokens=6))
    assert gathered.stats["decode_attn_kernel_steps"] == 0 < gathered.stats["decode_attn_gather_steps"]

    choose = paged.latent_decode_attention
    monkeypatch.setattr(
        paged, "latent_decode_attention",
        lambda cfg, bs, mesh, interpret, scale: choose(cfg, bs, mesh, True, scale),
    )
    monkeypatch.setattr(paged, "decode_attends_in_place", lambda cfg, bs, mesh=None: True)
    eng = LLMEngine(llm_config())
    saved = GLOBAL_CONFIG.flightrec
    GLOBAL_CONFIG.flightrec = True
    flightrec.reset()
    try:
        got = eng.generate(ps, SamplingParams(max_tokens=6))
        events = [e for r in flightrec.snapshot(planes=("llm",))["rings"].values()
                  for e in r["events"]]
    finally:
        GLOBAL_CONFIG.flightrec = saved
        flightrec.reset()
    assert [o["token_ids"] for o in got] == [o["token_ids"] for o in want]
    assert eng.stats["decode_attn_gather_steps"] == 0
    assert eng.stats["decode_attn_kernel_steps"] == gathered.stats["decode_attn_gather_steps"]
    steps = [e["extra"] for e in events if e["phase"] == "llm.decode_step"]
    assert steps
    for x in steps:
        assert x["latent_rows_read"] == x["kv_blocks_live"] * 16
        assert x["latent_rows_live"] <= x["latent_rows_read"] < x["latent_rows_live"] + 16 * x["batch"]
