"""Speculative decoding: draft-propose / target-verify on the decode tier.

Round-16 tentpole coverage, leg 2: a small draft model proposes k greedy
tokens per engine step, the target verifies them in one batched forward
(paged_verify), and greedy outputs are CI-pinned
bit-identical to vanilla decode. RAY_TPU_SPEC_DECODE=0 restores the
round-12 engine byte-identically.
"""

import dataclasses
import pickle

import pytest

from ray_tpu.core.config import GLOBAL_CONFIG
from ray_tpu.llm.config import LLMConfig, SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models.gpt2 import GPT2Config
from ray_tpu.models.llama import LlamaConfig


def _model(family="gpt2"):
    if family == "llama":
        return LlamaConfig.tiny(
            n_layer=2, d_model=64, n_head=4, n_kv_head=2, max_seq=256
        )
    return GPT2Config.tiny(n_layer=2, d_model=64, n_head=2, max_seq=256)


def _draft():
    return GPT2Config.tiny(n_layer=1, d_model=32, n_head=2, max_seq=256)


def _first_layer_draft(target, tmp_path):
    """A draft cut from the target: its first layer between its embedding
    and its head, as ``draft_weights_path`` loads it. A random Llama draft
    agrees with a random target nowhere (no tied embedding pulls both to
    the last token, as GPT-2's does), and a step must accept some and
    reject some for both halves of the verification to run."""
    import jax
    import numpy as np

    from ray_tpu.models import llama

    params = llama.init_params(jax.random.key(_cfg().seed), target)
    params["blocks"] = jax.tree.map(lambda a: a[:1], params["blocks"])
    path = tmp_path / "draft.pkl"
    path.write_bytes(pickle.dumps(jax.tree.map(np.asarray, params)))
    return {
        "draft_model_config": dataclasses.replace(target, n_layer=1),
        "draft_weights_path": str(path),
    }


def _cfg(**kw):
    defaults = dict(
        model_config=_model(),
        max_slots=4,
        max_seq=256,
        prefill_buckets=(16, 32, 64, 128, 256),
        prefix_chunk=16,
        max_prefix_cache_tokens=512,
    )
    defaults.update(kw)
    return LLMConfig(**defaults)


PROMPTS = [
    list(range(2, 60)),  # long
    list(range(3, 20)),  # short
    list(range(5, 40)),  # medium — three slots share every spec step
]
GREEDY = SamplingParams(max_tokens=12, temperature=0.0)


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_greedy_spec_decode_token_identical(family, tmp_path):
    """The tentpole contract: speculative decoding is a THROUGHPUT change,
    not a sampling change — greedy outputs bit-equal vanilla decode for
    both key/value families (draft and target of one family), while the
    spec counters prove speculation ran."""
    kw = {"model_config": _model(family)}
    van = LLMEngine(_cfg(**kw))
    out_v = [r["token_ids"] for r in van.generate(PROMPTS, GREEDY)]
    draft = (
        _first_layer_draft(kw["model_config"], tmp_path)
        if family == "llama"
        else {"draft_model_config": _draft()}
    )
    spec = LLMEngine(_cfg(spec_decode_tokens=4, **draft, **kw))
    out_s = [r["token_ids"] for r in spec.generate(PROMPTS, GREEDY)]
    assert out_s == out_v
    assert van.stats["spec_steps"] == 0
    assert spec.stats["spec_steps"] >= 1
    assert 0 < spec.stats["spec_accepted"] < spec.stats["spec_drafted"]
    # Fewer engine steps than tokens generated: speculation actually
    # compressed the decode loop (vanilla needs one step per token).
    assert spec._steps < van._steps


def test_perfect_draft_accepts_everything():
    """draft == target (same config, same seed -> identical params):
    every budget-eligible proposal verifies, accept rate 1.0, and the
    step count collapses toward tokens/(k+1)."""
    spec = LLMEngine(
        _cfg(spec_decode_tokens=4, draft_model_config=_model())
    )
    van = LLMEngine(_cfg())
    out_v = [r["token_ids"] for r in van.generate(PROMPTS, GREEDY)]
    out_s = [r["token_ids"] for r in spec.generate(PROMPTS, GREEDY)]
    assert out_s == out_v
    assert spec._spec.accept_rate() == 1.0
    assert spec.stats["spec_accepted"] == spec.stats["spec_drafted"] > 0


def test_spec_decode_kill_switch_restores_vanilla():
    """RAY_TPU_SPEC_DECODE=0 (the knob): the engine builds no draft
    model at all — the one-flag flip back to the round-12 engine."""
    old = GLOBAL_CONFIG.spec_decode
    GLOBAL_CONFIG.spec_decode = False
    try:
        eng = LLMEngine(
            _cfg(spec_decode_tokens=4, draft_model_config=_draft())
        )
        assert eng._spec is None
        out = [r["token_ids"] for r in eng.generate(PROMPTS, GREEDY)]
    finally:
        GLOBAL_CONFIG.spec_decode = old
    van = LLMEngine(_cfg())
    assert out == [r["token_ids"] for r in van.generate(PROMPTS, GREEDY)]
    assert eng.stats["spec_steps"] == 0
    assert eng._steps == van._steps  # step-for-step the same loop


def test_sampled_requests_never_speculate():
    """Spec steps require an all-greedy batch: a temperature>0 request
    in flight forces the vanilla program (speculative verification is a
    greedy-argmax contract)."""
    eng = LLMEngine(
        _cfg(spec_decode_tokens=4, draft_model_config=_draft())
    )
    eng.generate(
        [PROMPTS[0]], SamplingParams(max_tokens=8, temperature=0.8)
    )
    assert eng.stats["spec_steps"] == 0
    # Greedy traffic afterwards speculates again.
    eng.generate([PROMPTS[1]], GREEDY)
    assert eng.stats["spec_steps"] >= 1


def test_near_max_seq_falls_back_to_vanilla_steps():
    """A slot within k rows of max_seq makes the batch spec-ineligible
    (the verify program's writes must stay inside the block table):
    outputs stay identical, nothing corrupts."""
    model = _model()
    kw = dict(
        model_config=model,
        max_slots=2,
        max_seq=256,
        prefill_buckets=(64, 256),
        prefix_chunk=16,
        max_prefix_cache_tokens=512,
    )
    # 252 tokens: positions start at 252 > max_seq-1-k = 251, so NO step
    # is ever spec-eligible — the whole request decodes vanilla.
    prompt = list(range(2, 254))
    s = SamplingParams(max_tokens=6, temperature=0.0)
    van = LLMEngine(LLMConfig(**kw))
    out_v = van.generate([prompt], s)[0]["token_ids"]
    spec = LLMEngine(
        LLMConfig(**kw, spec_decode_tokens=4, draft_model_config=_draft())
    )
    out_s = spec.generate([prompt], s)[0]["token_ids"]
    assert out_s == out_v
    assert spec.stats["spec_steps"] == 0  # every step was vanilla
    # One row earlier (248 tokens), the first steps ARE eligible and the
    # boundary still holds by token identity.
    prompt2 = list(range(2, 250))
    van2 = LLMEngine(LLMConfig(**kw))
    spec2 = LLMEngine(
        LLMConfig(**kw, spec_decode_tokens=4, draft_model_config=_draft())
    )
    assert (
        spec2.generate([prompt2], s)[0]["token_ids"]
        == van2.generate([prompt2], s)[0]["token_ids"]
    )
    assert spec2.stats["spec_steps"] >= 1


def test_spec_with_chunked_prefill_and_prefix_cache():
    """Speculation composes with the round-12 scheduling features: the
    chunked-prefill interleave and pooled-prefix reuse change WHEN work
    happens, speculation changes how many tokens a step yields — greedy
    outputs stay pinned across the whole matrix."""
    shared = list(range(2, 50))
    batch1 = [shared + [61, i] for i in range(3)]
    batch2 = [shared + [62, i] for i in range(3)]  # 2nd wave hits the pool
    s = SamplingParams(max_tokens=10, temperature=0.0)
    van = LLMEngine(_cfg())
    out_v = [
        r["token_ids"]
        for b in (batch1, batch2)
        for r in van.generate(b, s)
    ]
    spec = LLMEngine(
        _cfg(
            spec_decode_tokens=3,
            draft_model_config=_draft(),
            prefill_chunk_tokens=16,
        )
    )
    out_s = [
        r["token_ids"]
        for b in (batch1, batch2)
        for r in spec.generate(b, s)
    ]
    assert out_s == out_v
    assert spec.stats["prefix_hits"] >= 1  # the cache actually engaged
    assert spec.stats["prefill_chunks"] >= 1  # chunking engaged too
    assert spec.stats["spec_steps"] >= 1


def test_draft_config_validation():
    with pytest.raises(ValueError, match="draft_model_config"):
        LLMEngine(_cfg(spec_decode_tokens=4))
    import dataclasses

    bad_vocab = dataclasses.replace(
        _draft(), vocab_size=_model().vocab_size + 1
    )
    with pytest.raises(ValueError, match="vocab"):
        LLMEngine(_cfg(spec_decode_tokens=4, draft_model_config=bad_vocab))


def test_spec_counters_reach_registry():
    from ray_tpu.util.metrics import registry, runtime_catalog

    assert "raytpu_llm_spec_drafted_total" in runtime_catalog()

    def totals():
        out = {"d": 0.0, "a": 0.0}
        for n, _t, v in registry().snapshot()["points"]:
            if n == "raytpu_llm_spec_drafted_total":
                out["d"] += v
            elif n == "raytpu_llm_spec_accepted_total":
                out["a"] += v
        return out

    before = totals()
    eng = LLMEngine(
        _cfg(spec_decode_tokens=4, draft_model_config=_model())
    )
    eng.generate([PROMPTS[0]], GREEDY)
    after = totals()
    assert after["d"] > before["d"]
    assert after["a"] > before["a"]


def test_draft_weights_path_loads_trained_draft(tmp_path):
    """draft_weights_path restores a pickled draft-params pytree (the
    ROADMAP leftover: the accept-rate gauge is only meaningful with a
    trained draft — random init stays the default). The loaded draft's
    params land verbatim (not the seed's random init), and greedy
    outputs remain token-identical to vanilla decode — verification
    makes draft QUALITY a throughput knob, never a correctness one."""
    import pickle

    import jax
    import numpy as np

    donor = LLMEngine(_cfg(spec_decode_tokens=4, draft_model_config=_draft()))
    ckpt = tmp_path / "draft.pkl"
    with open(ckpt, "wb") as f:
        pickle.dump(
            jax.tree.map(np.asarray, donor._spec.params), f
        )

    # A different engine seed would re-randomize the draft — the
    # checkpoint must win over the seed.
    loaded = LLMEngine(
        _cfg(
            spec_decode_tokens=4,
            draft_model_config=_draft(),
            draft_weights_path=str(ckpt),
            seed=7,
        )
    )
    random7 = LLMEngine(
        _cfg(spec_decode_tokens=4, draft_model_config=_draft(), seed=7)
    )
    donor_leaves = jax.tree.leaves(donor._spec.params)
    loaded_leaves = jax.tree.leaves(loaded._spec.params)
    for a, b in zip(donor_leaves, loaded_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(random7._spec.params), loaded_leaves)
    )

    # Correctness unchanged: greedy == vanilla, speculation still ran.
    van = LLMEngine(_cfg(seed=7))
    out_v = [r["token_ids"] for r in van.generate(PROMPTS, GREEDY)]
    out_l = [r["token_ids"] for r in loaded.generate(PROMPTS, GREEDY)]
    assert out_l == out_v
    assert loaded.stats["spec_steps"] > 0
