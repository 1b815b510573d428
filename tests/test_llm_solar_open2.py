"""Solar Open 2 through ``LLMEngine``: keys and values per head in the block
pool, a delta-rule state and a convolution tail per slot beside them, under
continuous batching at a tiny size on the CPU. Logits against the plain
reference's full forward; a request's greedy tokens whatever slot it gets and
whoever shares its steps; prefill in chunks between decode turns against whole
prefill; what the engine refuses or bypasses for this family, by name; the
span fields the family and the engine write; and a prompt longer than the
largest prefill bucket kept whole where the engine prefills in chunks.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import solar_open2_ref as ref  # noqa: E402
from ray_tpu.core.config import GLOBAL_CONFIG  # noqa: E402
from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.models import solar_open2 as so  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.util import flightrec  # noqa: E402
from test_solar_open2 import ref_config  # noqa: E402

pytestmark = pytest.mark.timeout(300)


def llm_config(**kw):
    return LLMConfig(**{
        "model_config": so.SolarOpen2Config.tiny(max_seq=128), "max_slots": 3,
        "max_seq": 128, "prefill_buckets": (32, 64, 128), "kv_block_size": 16,
        "prefix_chunk": 16, "seed": 0, "enable_prefix_caching": False, **kw,
    })


def prompts(n, rng=None, lo=9, hi=60):
    rng = rng or np.random.default_rng(1)
    return [rng.integers(3, 500, size=int(rng.integers(lo, hi))).tolist() for _ in range(n)]


def generate(engine, prompt, max_tokens=6):
    return engine.generate([prompt], SamplingParams(max_tokens=max_tokens))[0]["token_ids"]


def recorded(engine, run):
    """The ``llm`` events the flight recorder holds after ``run()``."""
    saved = GLOBAL_CONFIG.flightrec
    GLOBAL_CONFIG.flightrec = True
    flightrec.reset()
    try:
        run()
        return [e for r in flightrec.snapshot(planes=("llm",))["rings"].values() for e in r["events"]]
    finally:
        GLOBAL_CONFIG.flightrec = saved
        flightrec.reset()


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
        want = ref.forward(engine.params, jnp.asarray(toks, jnp.int32), c)
        got = np.stack(seen[out["request_id"]])
        assert got.shape == (5, engine.model_config.vocab_size)
        np.testing.assert_allclose(got, want[len(p) - 1 : len(p) + 4], rtol=2e-3, atol=2e-5)


def test_greedy_tokens_do_not_depend_on_slot_history_or_company(engine):
    """Alone; in a full batch (three slots, five requests, so two of them
    start in a slot another request has just left); and alone again in a
    used engine: the same tokens."""
    ps = prompts(5, np.random.default_rng(2))
    alone = [generate(LLMEngine(llm_config()), p) for p in ps[:2]]
    resets = engine.stats["state_resets"]
    together = engine.generate(ps, SamplingParams(max_tokens=6))
    assert engine.stats["state_resets"] == resets + 5
    assert [o["token_ids"] for o in together[:2]] == alone
    for p, o in zip(ps, together):  # every slot has been used by now
        assert generate(engine, p) == o["token_ids"]


def test_a_reused_slot_starts_from_zero_state():
    """A request in the slot a longer one just left gives what it gives in a
    fresh engine, although the state row still held the other's state."""
    eng = LLMEngine(llm_config(max_slots=1))
    a, b = prompts(2, np.random.default_rng(3))
    generate(eng, a, max_tokens=9)
    assert np.abs(np.asarray(eng.pool["state"][:, 0])).max() > 0  # not cleared on release ...
    assert generate(eng, b) == generate(LLMEngine(llm_config(max_slots=1)), b)  # ... but at prefill


def _chunk_turns(eng, ids):
    """Step ``eng`` until nothing is left; per step, the request whose prompt
    moved on (None where no chunk ran) and the rows that were decoding."""
    turns = []
    while eng.has_unfinished():
        before = {i: eng.requests[i].pf_next for i in ids if i in eng.requests}
        decoding = sum(r is not None and not r.prefilling for r in eng._slot_req)
        eng.step()
        moved = [i for i, at in before.items() if eng.requests[i].pf_next != at]
        assert len(moved) <= 1  # one chunk a turn at most
        turns.append((moved[0] if moved else None, decoding))
    eng.pop_finished()
    return turns


def test_a_chunk_goes_to_the_prompt_with_fewest_tokens_left():
    """Four prompts waiting together: the shortest is prefilled first and
    whole, then the next; among equals the one that came first."""
    eng = LLMEngine(llm_config(prefill_chunk_tokens=16, max_slots=4, num_kv_blocks=4 * 8 + 1))
    rng = np.random.default_rng(7)
    lengths = {"long": 100, "short": 40, "mid": 70, "late": 40}
    for rid, n in lengths.items():
        eng.add_request(rid, rng.integers(3, 500, size=n).tolist(), SamplingParams(max_tokens=3))
    order = [rid for rid, _rows in _chunk_turns(eng, list(lengths)) if rid is not None]
    runs = [rid for i, rid in enumerate(order) if i == 0 or order[i - 1] != rid]
    assert runs == ["short", "late", "mid", "long"]  # each prompt's chunks in one run: none begun and left
    assert [order.count(rid) for rid in runs] == [3, 3, 5, 7]


def test_a_long_prompt_is_passed_over_by_a_bounded_number_of_chunks(monkeypatch):
    """Short prompts keep arriving into the free slots beside a long one:
    each chunk that goes to them counts for the long one, which is served
    after five of them and then to its end."""
    from ray_tpu.llm import engine as engine_module

    monkeypatch.setattr(engine_module, "_CHUNKS_PASSED_A_CHUNK", 2)  # 8 tokens a chunk passed
    eng = LLMEngine(llm_config(prefill_chunk_tokens=16, num_kv_blocks=3 * 8 + 1))
    rng = np.random.default_rng(9)
    draw = lambda n: rng.integers(3, 500, size=n).tolist()  # noqa: E731
    eng.add_request("long", draw(112), SamplingParams(max_tokens=1))
    sent, order = 0, []
    while "long" not in {r.request_id for r in eng.pop_finished()}:
        while sum(r.slot < 0 or r.prefilling for r in eng.requests.values() if not r.finished) < 3:
            eng.add_request(f"short{sent}", draw(32), SamplingParams(max_tokens=1))
            sent += 1
        before = {i: r.pf_next for i, r in eng.requests.items()}
        eng.step()
        order += [i for i, at in before.items() if i in eng.requests and eng.requests[i].pf_next != at]
        assert len(order) < 200
    first = order.index("long")
    # 112 - 32 tokens at 8 a chunk passed, and a short one that has begun ends first; then the fewest left
    assert 10 <= first <= 14 and order[first:] == ["long"] * 7, order


def test_decode_turns_come_between_chunks_while_half_the_slots_decode():
    """With two of three slots decoding, a chunk follows three turns that
    ran none; with fewer rows decoding every turn runs one."""
    from ray_tpu.llm import engine as engine_module

    eng = LLMEngine(llm_config(prefill_chunk_tokens=16, num_kv_blocks=3 * 8 + 1))
    rng = np.random.default_rng(8)
    draw = lambda n: rng.integers(3, 500, size=n).tolist()  # noqa: E731
    for rid in ("a", "b"):
        eng.add_request(rid, draw(20), SamplingParams(max_tokens=40))
    while not all(eng.requests[r].generated for r in ("a", "b")):
        eng.step()
    eng.add_request("c", draw(100), SamplingParams(max_tokens=2))
    turns = _chunk_turns(eng, ["c"])
    at = [i for i, (rid, _rows) in enumerate(turns) if rid == "c"]
    assert len(at) == 7 and all(turns[i][1] == 2 for i in at)
    assert {b - a for a, b in zip(at, at[1:])} == {engine_module._DECODE_TURNS_A_CHUNK + 1}
    # One row decoding of three: nothing holds the chunks back.
    eng.add_request("d", draw(20), SamplingParams(max_tokens=40))
    while not eng.requests["d"].generated:
        eng.step()
    eng.add_request("e", draw(100), SamplingParams(max_tokens=2))
    turns = _chunk_turns(eng, ["e"])
    assert [rid for rid, _rows in turns[:8]] == [None] + ["e"] * 7  # admitted in the first turn


def test_chunked_prefill_between_decode_turns_is_whole_prefill():
    """16-token chunks carry state, tail and rows of keys and values from
    chunk to chunk; the decode steps of other requests between the chunks
    leave a slot that is mid-prefill alone (it is not live in them), also
    where such a step was launched ahead, before the slot's next chunk."""
    ps = prompts(4, np.random.default_rng(4), lo=70, hi=120)
    whole = LLMEngine(llm_config()).generate(ps, SamplingParams(max_tokens=6))
    eng = LLMEngine(llm_config(prefill_chunk_tokens=16, num_kv_blocks=3 * 8 + 1))  # every slot its whole table
    chunked: list = []
    events = recorded(eng, lambda: chunked.extend(eng.generate(ps, SamplingParams(max_tokens=6))))
    assert eng.stats["prefill_chunks"] >= 4 * 5
    assert [o["token_ids"] for o in chunked] == [o["token_ids"] for o in whole]
    steps = [e["extra"] for e in events if e["phase"] == "llm.decode_step"]
    with_chunk = [x for x in steps if x["chunks_pending"] > 0]
    assert with_chunk and len(with_chunk) < len(steps)  # three slots, four requests: both kinds of turn
    assert any(x["ahead"] for x in with_chunk)  # a slot mid-prefill does not make a turn synchronous
    chunks = [e["extra"] for e in events if e["phase"] == "llm.prefill_chunk"]
    assert len(chunks) == eng.stats["prefill_chunks"]
    for x in chunks:  # began from the slot's state, or from zero
        assert x["state_carried"] == int(x["start"] > 0) and x["tokens"] <= x["bucket"]
        assert x["picks"] == x["tokens"] * 2 * eng.model_config.n_moe_layers and x["experts_touched"] > 0
    assert sum(x["state_carried"] == 0 for x in chunks) == 4 == eng.stats["state_resets"]


@pytest.mark.parametrize("chunk, blocks", [(0, 8), (16, 10)], ids=["whole", "in_chunks"])
def test_a_prefill_counts_the_blocks_it_writes_whole_and_a_decode_step_none(chunk, blocks):
    """A prompt of 70 tokens in blocks of 16: one program of the 128 bucket
    writes eight blocks a tensor of the GQA layer, five chunks in the 32
    bucket two each (``paged._write_blocks``); the decode steps behind them
    add nothing, and the greedy tokens are the reference's either way."""
    eng = LLMEngine(llm_config(prefill_chunk_tokens=chunk, num_kv_blocks=3 * 8 + 1))
    p = prompts(1, np.random.default_rng(5), lo=70, hi=71)[0]
    tokens = generate(eng, p)
    assert eng.stats["prefill_blocks_written"] == eng.stats["prefill_tokens_padded"] // 16 == blocks
    assert eng.stats["prefill_chunks"] == (5 if chunk else 0) and eng.stats["tokens_generated"] == len(tokens)
    want = ref.forward(eng.params, jnp.asarray(p + tokens, jnp.int32), ref_config(eng.model_config))
    assert tokens == np.argmax(want[69 : 69 + len(tokens)], axis=-1).tolist()


def test_padded_bucket_tails_leave_the_state_alone():
    """The same prompts through one wide bucket (every prompt padded to 128)
    and through the ladder."""
    ps = prompts(3, np.random.default_rng(5))
    ladder = LLMEngine(llm_config()).generate(ps, SamplingParams(max_tokens=6))
    wide = LLMEngine(llm_config(prefill_buckets=(128,))).generate(ps, SamplingParams(max_tokens=6))
    assert [o["token_ids"] for o in wide] == [o["token_ids"] for o in ladder]


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
        assert "solar_open2" in str(e.value) and "recurrent state" in str(e.value)
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


def test_spans_and_counters_of_the_experts_the_state_and_the_rows(engine):
    events = recorded(engine, lambda: engine.generate(prompts(2), SamplingParams(max_tokens=4)))
    cfg = engine.model_config
    steps = [e["extra"] for e in events if e["phase"] == "llm.decode_step"]
    fills = [e["extra"] for e in events if e["phase"] == "llm.prefill"]
    assert steps and len(fills) == 2
    W = engine.block_tables.shape[1]
    for x in steps:
        assert x["state_slots"] == x["batch"]
        assert x["picks"] == x["batch"] * cfg.experts_per_token * cfg.n_moe_layers
        assert x["picks_here"] == x["picks"]  # all eight experts are held
        assert 0 < x["experts_touched"] <= x["experts_held"] == 8 * cfg.n_moe_layers
        # on the CPU the program gathers every table whole
        assert x["kv_rows_read"] == 3 * W * 16 and 0 < x["kv_rows_live"] <= x["kv_blocks_live"] * 16
        assert "chunks_pending" not in x  # this engine prefills no prompt in chunks
    for x in fills:
        assert x["state_slots"] == 1 and x["picks_here"] == x["picks"] and x["state_carried"] == 0
        assert x["picks"] == x["tokens"] * cfg.experts_per_token * cfg.n_moe_layers
        assert "kv_rows_live" not in x
    for part in ("k", "v", "state", "conv"):
        assert engine.stats[f"cache_bytes_{part}"] == engine.pool[part].nbytes
    assert engine.stats["cache_bytes_state"] == 3 * (3 + 1) * cfg.kda_heads * cfg.kda_head_dim**2 * 4
    assert engine.stats["decode_attn_gather_steps"] > 0 == engine.stats["decode_attn_kernel_steps"]


def test_a_family_without_a_state_writes_no_state_carried():
    eng = LLMEngine(llm_config(model_config=LlamaConfig.tiny(max_seq=128), prefill_chunk_tokens=16))
    events = recorded(eng, lambda: eng.generate(prompts(1, lo=40, hi=41), SamplingParams(max_tokens=3)))
    chunks = [e["extra"] for e in events if e["phase"] == "llm.prefill_chunk"]
    assert chunks and all("state_carried" not in x for x in chunks)
    steps = [e["extra"] for e in events if e["phase"] == "llm.decode_step"]
    assert steps and all(x["chunks_pending"] == 0 for x in steps)  # one request: it decodes once it has prefilled


@pytest.mark.parametrize("family", ["solar_open2", "llama"])
def test_a_prompt_past_the_largest_bucket_is_kept_whole_where_the_engine_prefills_in_chunks(family):
    """The default-like ladder tops out at 32 here. With ``prefill_chunk_tokens``
    a prompt of 100 tokens is served whole, in chunks, and gives the tokens an
    engine with a bucket that holds it gives; only what ``max_seq`` cannot hold
    beside the answer is still cut, and counted. Without chunks the cut is to
    the largest bucket, as before."""
    model = so.SolarOpen2Config.tiny(max_seq=128) if family == "solar_open2" else LlamaConfig.tiny(max_seq=128)
    (p,) = prompts(1, np.random.default_rng(7), lo=100, hi=101)
    want = generate(LLMEngine(llm_config(model_config=model)), p)
    eng = LLMEngine(llm_config(model_config=model, prefill_buckets=(16, 32), prefill_chunk_tokens=16))
    assert generate(eng, p) == want
    assert eng.stats["prompts_truncated"] == 0 and eng.stats["prefill_tokens"] == 100
    # 128 positions hold the answer's 6 tokens and 122 of a longer prompt's
    (long,) = prompts(1, np.random.default_rng(8), lo=150, hi=151)
    assert generate(eng, long) == generate(eng, long[-122:])
    assert eng.stats["prompts_truncated"] == 1 and eng.stats["prefill_tokens"] == 100 + 2 * 122
    cut = LLMEngine(llm_config(model_config=model, prefill_buckets=(16, 32)))
    assert generate(cut, p) == generate(cut, p[-32:]) and cut.stats["prompts_truncated"] == 1
