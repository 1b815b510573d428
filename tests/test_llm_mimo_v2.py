"""The ``mimo_v2`` family through ``LLMEngine``: two kinds of layer that differ
in shape under a block table each, the window kind's blocks given back while
requests run, at a tiny size on the CPU (a window of 6 over blocks of 4, chunks
of 8, contexts of 40 and more). Logits against the plain reference's full
forward; the bound on a slot's window blocks; what the engine refuses for the
family, by name and for its own reason; the OpenAI app; its spans and counters,
the bytes of a row by kind and of the pool as laid among them.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import mimo_v2_ref as ref  # noqa: E402
from ray_tpu.core.config import GLOBAL_CONFIG  # noqa: E402
from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.models import mimo_v2  # noqa: E402
from ray_tpu.util import flightrec  # noqa: E402
from test_mimo_v2 import ref_config  # noqa: E402

pytestmark = pytest.mark.timeout(300)
BLOCK, CHUNK = 4, 8


def llm_config(**kw):
    return LLMConfig(**{
        "model_config": mimo_v2.MimoV2Config.tiny(max_seq=128), "max_slots": 3, "max_seq": 128,
        "prefill_buckets": (8, 16, 64, 128), "prefill_chunk_tokens": CHUNK, "kv_block_size": BLOCK,
        "num_kv_blocks": 3 * 32 + 1, "prefix_chunk": 16, "seed": 0, **kw,
    })


def prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 500, size=n).tolist() for n in lengths]


@pytest.fixture(scope="module")
def engine():
    return LLMEngine(llm_config())


def run(engine, ps, answers, after_step=None):
    """The requests to their ends, the logits the engine samples from noted
    by request; ``after_step`` is called after every step."""
    seen: dict = {}
    sample = engine._sample

    def recording(logits, req):
        seen.setdefault(req.request_id, []).append(np.array(logits))
        return sample(logits, req)

    engine._sample = recording
    try:
        ids = [f"t{engine._steps}-{i}" for i in range(len(ps))]
        for rid, p, n in zip(ids, ps, answers):
            engine.add_request(rid, p, SamplingParams(max_tokens=n, stop_token=-1))
        while engine.has_unfinished():
            engine.step()
            if after_step is not None:
                after_step()
        done = {r.request_id: r for r in engine.pop_finished()}
    finally:
        engine._sample = sample
    return [done[rid] for rid in ids], [np.stack(seen[rid]) for rid in ids]


def test_short_and_long_requests_in_chunks_are_the_reference_and_the_window_stays_bounded(engine):
    """Five requests of 7 to 60 tokens over three slots, the long ones
    prefilled in chunks of 8 (each longer than the window of 6) between the
    others' decode steps: every logits row the engine samples from is the
    reference's (2e-4, float32 on both sides), though blocks one slot gave
    back were written by another meanwhile; no slot ever holds more than
    ceil((6 + 8) / 4) + 1 window blocks; the window part's free list is
    conserved at every step; and the two parts of the pool have their kinds'
    heads."""
    w = engine._window
    assert w.per_slot == 5 and w.mgr.num_blocks == 3 * 5 + 1
    assert engine.pool["window"]["k"].shape == (3, w.mgr.num_blocks, 2, BLOCK, 24)
    assert engine.pool["full"]["v"].shape == (2, 3 * 32 + 1, 1, BLOCK, 16)
    held_most, owners = [0], {}

    def check():
        held = [len(h) for h in w._held]
        held_most[0] = max(held_most[0], *held)
        assert w.mgr.free_blocks + sum(held) == w.mgr.num_blocks - 1
        for slot, h in enumerate(w._held):
            for b in h:
                owners.setdefault(b, set()).add(engine._slot_req[slot].request_id)

    lens, answers = [50, 7, 33, 60, 9], [20, 30, 10, 5, 9]
    ps = prompts(lens)
    released = engine.stats["window_blocks_released"]
    done, logits = run(engine, ps, answers, check)
    assert 3 <= held_most[0] <= w.per_slot
    assert engine.stats["window_blocks_released"] - released >= 40
    assert max(len(o) for o in owners.values()) >= 2  # a block one request gave back, another held
    assert w.mgr.free_blocks == w.mgr.num_blocks - 1 and not w.tables.any()
    c = ref_config(engine.model_config)
    for p, r, got in zip(ps, done, logits):
        assert r.error is None and len(r.generated) == len(got)
        toks = jnp.asarray(p + r.generated, jnp.int32)
        want = ref.forward(engine.params, toks, c)
        np.testing.assert_allclose(got, want[len(p) - 1 : len(p) - 1 + len(got)], rtol=2e-4, atol=2e-5)
    assert engine.stats["prompts_truncated"] == 0 and engine.stats["prefill_chunks"] > 0


@pytest.mark.parametrize("chunk, blocks", [(0, 16), (CHUNK, 6)], ids=["whole", "in_chunks"])
def test_a_prefill_counts_the_blocks_it_writes_whole_and_a_decode_step_none(chunk, blocks):
    """A prompt of 20 tokens in blocks of 4: one program of the 64 bucket
    writes sixteen blocks a tensor a layer of its kind, three chunks in the 8
    bucket two each (``paged._write_blocks``, both pool parts under their own
    tables); the decode steps behind them add nothing, and every logits row
    the engine samples from is the reference's either way."""
    eng = LLMEngine(llm_config(prefill_chunk_tokens=chunk))
    ps = prompts([20], seed=9)
    done, logits = run(eng, ps, [5])
    assert eng.stats["prefill_blocks_written"] == eng.stats["prefill_tokens_padded"] // BLOCK == blocks
    assert eng.stats["prefill_chunks"] == (3 if chunk else 0) and eng.stats["tokens_generated"] == 5
    toks = jnp.asarray(ps[0] + done[0].generated, jnp.int32)
    want = ref.forward(eng.params, toks, ref_config(eng.model_config))
    np.testing.assert_allclose(logits[0], want[19:24], rtol=2e-4, atol=2e-5)
    assert done[0].generated == np.argmax(want[19:24], axis=-1).tolist()


def test_greedy_tokens_run_ahead_and_do_not_depend_on_company(engine):
    ps = prompts([41, 12, 30], seed=3)
    sampling = SamplingParams(max_tokens=24, stop_token=-1)
    alone = [engine.generate([p], sampling)[0]["token_ids"] for p in ps]
    ahead = engine.stats["decode_steps_ahead"]
    together = [o["token_ids"] for o in engine.generate(ps, sampling)]
    assert together == alone
    assert engine.stats["decode_steps_ahead"] > ahead


@pytest.mark.parametrize("what, kw, match", [
    ("speculative verification", {"spec_decode_tokens": 2}, "spec_decode_tokens"),
    ("tensor parallelism", {"tensor_parallelism": 2}, "tensor_parallelism"),
    ("the disaggregated export", "prefill_only", "prefill_only"),
    ("the disaggregated import", "handoff", "handoff"),
])
def test_what_the_engine_cannot_do_for_this_family_is_said_with_its_own_reason(engine, what, kw, match):
    if isinstance(kw, dict):
        with pytest.raises(ValueError, match=match) as e:
            LLMEngine(llm_config(**kw))
    elif kw == "prefill_only":
        with pytest.raises(ValueError, match=match) as e:
            engine.add_request("x", [1, 2, 3], prefill_only=True)
    else:
        with pytest.raises(ValueError, match=match) as e:
            engine.add_handoff_request("x", {"prompt": [1, 2, 3]})
    assert "'mimo_v2' keeps a block table per layer kind" in str(e.value)
    assert "recurrent state" not in str(e.value)


def test_a_repeated_prompt_bypasses_the_prefix_cache_and_is_counted():
    eng = LLMEngine(llm_config())
    p = prompts([40], seed=4)[0]
    first, second = (eng.generate([p], SamplingParams(max_tokens=4))[0]["token_ids"] for _ in range(2))
    assert first == second
    assert eng.stats["prefix_cache_bypassed"] == 2 and eng.stats["prefix_lookups"] == 0
    assert not eng._prefix_pool


def test_the_engine_counts_the_pool_as_laid_and_as_the_mathematics_needs_it(engine):
    """At construction: each part's bytes, the same as the device lays them
    out (the CPU pads nothing), and what the kinds on the family's record price
    the same blocks at: (24 + 16) float32 a key/value head a position."""
    s = engine.stats
    laid = {k: v for k, v in s.items() if k.startswith("cache_bytes_laid_")}
    assert sorted(laid) == [f"cache_bytes_laid_{p}_{x}" for p in ("full", "window") for x in ("k", "v")]
    assert all(v == s[k.replace("_laid", "")] for k, v in laid.items())
    assert s["cache_bytes_needed_kind0"] == 97 * BLOCK * 2 * 160  # two full layers of one head
    assert s["cache_bytes_needed_kind1"] == 16 * BLOCK * 3 * 2 * 160  # three window layers of two
    assert sum(laid.values()) == s["cache_bytes_needed_kind0"] + s["cache_bytes_needed_kind1"]
    padded = LLMEngine(llm_config(model_config=mimo_v2.MimoV2Config.tiny(
        max_seq=128, head_dim=192, v_head_dim=128, rotary_dim=64)))
    s = padded.stats
    laid = sum(v for k, v in s.items() if k.startswith("cache_bytes_laid_"))
    needed = s["cache_bytes_needed_kind0"] + s["cache_bytes_needed_kind1"]
    assert laid / needed == pytest.approx((256 + 128) / (192 + 128))  # keys of 192 in rows of 256: a fifth over


def test_spans_carry_the_rows_by_kind(engine):
    saved = GLOBAL_CONFIG.flightrec
    GLOBAL_CONFIG.flightrec = True
    flightrec.reset()
    try:
        ps = prompts([45, 10, 6], seed=9)  # two in chunks, one whole
        engine.generate(ps, SamplingParams(max_tokens=12, stop_token=-1))
        events = sorted(
            (e for r in flightrec.snapshot(planes=("llm",))["rings"].values() for e in r["events"]),
            key=lambda e: e["t"],
        )
    finally:
        GLOBAL_CONFIG.flightrec = saved
        flightrec.reset()
    steps = [e["extra"] for e in events if e["phase"] == "llm.decode_step"]
    assert steps and all(
        {"kv_rows_full", "kv_rows_window", "kv_rows_window_read", "window_blocks_held",
         "blocks_full_retention", "experts_touched", "picks_here"} <= set(s) for s in steps
    )
    for s in steps:
        assert s["kv_rows_window"] <= s["batch"] * 6 and s["kv_rows_window"] <= s["kv_rows_full"]
    assert any(s["window_blocks_held"] < s["blocks_full_retention"] for s in steps)
    chunks = [e["extra"] for e in events if e["phase"] == "llm.prefill_chunk"]
    assert sorted({c["start"] for c in chunks if c["tokens"] == CHUNK}) == [0, 8, 16, 24, 32]
    assert all("experts_touched" in c and "picks_here" in c for c in chunks)


def test_the_openai_app_serves_the_family():
    """``build_openai_app`` over the family's engine: a completion and a chat
    answer come back through the served path."""
    import json
    import urllib.request

    import ray_tpu
    from ray_tpu.llm import build_openai_app
    from ray_tpu.serve import api as serve

    ray_tpu.init(num_cpus=4)
    try:
        serve.run(build_openai_app(llm_config(), name="mimo"))
        port = serve.proxy_port()

        def post(path, body):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"}, method="POST",
            )
            with urllib.request.urlopen(req, timeout=120) as r:
                return json.loads(r.read())

        # a prompt of 40 bytes: five chunks of 8 through the window layers' turning blocks
        out = post("/mimo/v1/completions", {"prompt": "window of 128, a sink, keys of 192 lanes", "max_tokens": 6})
        assert out["object"] == "text_completion" and out["usage"]["completion_tokens"] >= 1
        chat = post("/mimo/v1/chat/completions", {"messages": [{"role": "user", "content": "hey"}], "max_tokens": 4})
        assert chat["choices"][0]["message"]["role"] == "assistant"
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
