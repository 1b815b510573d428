"""Cache-aware LLM serving: prefix-affinity routing + chunked prefill.

Round-12 tentpole coverage: the serve router biases pow-2 toward the
replica whose ADVERTISED prefix-KV pool already holds the prompt's
leading blocks (digest contract in util/prefix_digest.py), and the
engine prefills long prompts in chunks interleaved with decode steps.
Both halves ship behind kill switches (RAY_TPU_PREFIX_ROUTING=0,
prefill_chunk_tokens=0) that restore the old paths byte-identically.
"""

import time

import pytest

from conftest import wait_for_condition
from ray_tpu.core.config import GLOBAL_CONFIG
from ray_tpu.llm.config import LLMConfig, SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models.gpt2 import GPT2Config
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.util.prefix_digest import (
    BYTE_BOS_SCHEME,
    chain_digests,
    prompt_digests,
)


def _tiny_config(family="gpt2", **kw):
    if family == "llama":
        model = LlamaConfig.tiny(
            n_layer=2, d_model=64, n_head=4, n_kv_head=2, max_seq=256
        )
    else:
        model = GPT2Config.tiny(n_layer=2, d_model=64, n_head=2, max_seq=256)
    defaults = dict(
        model_config=model,
        max_slots=4,
        max_seq=256,
        prefill_buckets=(16, 32, 64, 128, 256),
        prefix_chunk=16,
        max_prefix_cache_tokens=512,
    )
    defaults.update(kw)
    return LLMConfig(**defaults)


# -- digest contract ---------------------------------------------------------


def test_engine_and_router_digests_agree():
    """The engine's pooled-prefix advertisement and the router's
    text-side prompt hashing must meet in the middle: after one request
    pools a prefix, the router-computed digests of a same-prefix prompt
    match the advertised set (that match IS the routing signal)."""
    eng = LLMEngine(_tiny_config())
    shared = "SYSTEM: concise assistant. answer briefly please. Q: "
    eng.generate([shared + "first question"], SamplingParams(max_tokens=2))
    adv = eng.prefix_digest()
    assert adv["scheme"] == BYTE_BOS_SCHEME
    assert adv["chunk"] == 16
    assert adv["digests"] and adv["version"] >= 1
    got = prompt_digests(shared + "a different one", 16, BYTE_BOS_SCHEME)
    matched = [d for d in got if d in set(adv["digests"])]
    # The shared prefix spans >= 2 whole 16-byte blocks; all of them match.
    assert len(matched) >= 2
    # An unrelated prompt matches nothing.
    other = prompt_digests("totally unrelated text " * 4, 16, BYTE_BOS_SCHEME)
    assert not set(other) & set(adv["digests"])
    # Unknown scheme -> no text-side hashing at all (load-only fallback).
    assert prompt_digests(shared, 16, "custom") == []


def test_chain_digests_strict_vs_pool():
    ids = list(range(1, 49))  # 48 tokens, chunk 16
    strict = chain_digests(ids, 16)
    pool = chain_digests(ids, 16, strict=False)
    assert len(strict) == 2  # 16, 32 (strict: one token must remain)
    assert len(pool) == 3  # 16, 32, 48 (an entry's full length is servable)
    assert pool[:2] == strict  # same rolling chain


# -- config validation (satellite) -------------------------------------------


def test_chunk_knobs_validated_as_block_multiples():
    """prefix_chunk and prefill_chunk_tokens share one validation: both
    must be kv_block_size multiples, whatever the block size; 0 disables
    chunked prefill."""
    with pytest.raises(ValueError, match="multiple of kv_block_size"):
        LLMEngine(_tiny_config(prefix_chunk=24))  # not a 16-multiple
    with pytest.raises(ValueError, match="multiple of kv_block_size"):
        LLMEngine(_tiny_config(prefill_chunk_tokens=24))
    # Same shared message for both knobs.
    for kw in (dict(prefix_chunk=24), dict(prefill_chunk_tokens=24)):
        with pytest.raises(ValueError) as e:
            LLMEngine(_tiny_config(**kw))
        assert "block granularity" in str(e.value)
    # prefix_chunk only matters when prefix caching is on.
    LLMEngine(_tiny_config(prefix_chunk=24, enable_prefix_caching=False))
    # 0 = chunked prefill disabled, always valid.
    LLMEngine(_tiny_config(prefill_chunk_tokens=0))
    # The constraint follows the block size: 24 is a multiple of 8.
    LLMEngine(_tiny_config(kv_block_size=8, prefill_chunk_tokens=24))


# -- chunked prefill ---------------------------------------------------------


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_chunked_prefill_token_identical(family):
    """Chunked prefill is a scheduling change, not a math change: greedy
    outputs are identical to the unchunked path on CPU for both key/value
    families, while the chunk counter proves the chunked path actually ran."""
    kw = {"family": family}
    prompts = [
        list(range(2, 120)),  # long: chunks
        list(range(3, 20)),  # short: below one chunk, unchunked
        list(range(5, 100)),  # long again
    ]
    s = SamplingParams(max_tokens=6, temperature=0.0)
    off = LLMEngine(_tiny_config(**kw))
    on = LLMEngine(_tiny_config(prefill_chunk_tokens=32, **kw))
    out_off = [r["token_ids"] for r in off.generate(prompts, s)]
    out_on = [r["token_ids"] for r in on.generate(prompts, s)]
    assert out_on == out_off
    assert off.stats["prefill_chunks"] == 0
    assert on.stats["prefill_chunks"] >= 6  # 118->4 chunks, 95->3 chunks
    # Chunking never changes WHAT was prefilled, only when.
    assert on.stats["prefill_tokens"] == off.stats["prefill_tokens"]


def test_chunked_prefill_interleaves_decode():
    """A long prompt no longer stalls in-flight decoders: while it
    prefills chunk-by-chunk, an already-running request gains one token
    per engine step (the ITL-bounding property, in step units)."""
    eng = LLMEngine(_tiny_config(prefill_chunk_tokens=16))
    eng.add_request("short", list(range(2, 10)), SamplingParams(max_tokens=30))
    eng.step()  # admit + first token
    short = eng.requests["short"]
    long_prompt = list(range(2, 150))  # 148 tokens = 10 chunks of 16
    eng.add_request("long", long_prompt, SamplingParams(max_tokens=2))
    long_req = eng.requests["long"]
    steps_while_prefilling = 0
    while not long_req.generated:  # admitting / still prefilling
        before = len(short.generated)
        eng.step()
        assert len(short.generated) == before + 1  # decode every step
        steps_while_prefilling += 1
        assert steps_while_prefilling < 50
    assert steps_while_prefilling >= 5  # the prefill really was spread out
    assert eng.stats["prefill_chunks"] >= 5
    # The long request still completes correctly.
    while not long_req.finished:
        eng.step()
    assert len(long_req.generated) == 2


def test_chunked_prefill_full_width_table_no_corruption():
    """Regression (round-12 review): a near-max-seq prompt whose block
    table is FULL width (T + max_tokens >= max_seq) must not let a
    chunk's padded bucket rows clamp into the request's own last real
    block — positions past max_seq index table[W-1], NOT the scratch
    block. _chunk_bucket now refuses buckets reaching past max_seq (the
    request falls back to unchunked prefill), so outputs stay
    token-identical."""
    model = GPT2Config.tiny(n_layer=2, d_model=64, n_head=2, max_seq=256)
    kw = dict(
        model_config=model,
        max_slots=2,
        max_seq=256,
        prefill_buckets=(64, 256),
        prefix_chunk=16,
        max_prefix_cache_tokens=512,
    )
    prompt = list(range(2, 252))  # 250 tokens; +max_tokens fills the table
    s = SamplingParams(max_tokens=6, temperature=0.0)
    off = LLMEngine(LLMConfig(**kw))
    on = LLMEngine(LLMConfig(**kw, prefill_chunk_tokens=48))
    out_off = off.generate([prompt], s)[0]["token_ids"]
    out_on = on.generate([prompt], s)[0]["token_ids"]
    assert out_on == out_off
    # The final chunk (start 240) has no bucket fitting under max_seq,
    # so the whole prompt correctly fell back to unchunked prefill.
    assert on.stats["prefill_chunks"] == 0


def test_chunked_prefill_counter_in_catalog():
    from ray_tpu.util.metrics import registry, runtime_catalog

    assert "raytpu_llm_prefill_chunks_total" in runtime_catalog()
    before = 0.0
    for n, _t, v in registry().snapshot()["points"]:
        if n == "raytpu_llm_prefill_chunks_total":
            before = v
    eng = LLMEngine(_tiny_config(prefill_chunk_tokens=16))
    eng.generate([list(range(2, 100))], SamplingParams(max_tokens=2))
    after = 0.0
    for n, _t, v in registry().snapshot()["points"]:
        if n == "raytpu_llm_prefill_chunks_total":
            after = v
    assert after - before >= 5


# -- router unit behavior ----------------------------------------------------


class _FakeReplica:
    def __init__(self, rid):
        self._actor_id = rid


def _router(replicas, state=None, inflight=None):
    from ray_tpu.serve.router import Router

    r = Router.__new__(Router)
    r._controller = None
    r._deployment = "unit"
    r._replicas = replicas
    r._version = 1
    r._inflight = dict(inflight or {x._actor_id: 0 for x in replicas})
    r._recently_dead = {}
    r._model_replicas = {}
    r._listen_task = None
    r._affinity = "prompt_prefix"
    r._affinity_cfg = {"scheme": BYTE_BOS_SCHEME, "chunk": 16}
    r._replica_state = dict(state or {})
    r._state_fetched = time.monotonic() + 3600  # no background fetches
    r._state_task = None
    r._max_concurrent = 8
    return r


def _adv(digests, qlen=0):
    return {"queue_len": qlen, "age_s": 0.1, "state": {"digests": digests}}


def test_pick_prefix_longest_match_wins():
    a, b = _FakeReplica("a" * 12), _FakeReplica("b" * 12)
    digests = [101, 102, 103]
    router = _router(
        [a, b],
        state={
            "a" * 12: _adv([101]),  # 1 leading block
            "b" * 12: _adv([101, 102]),  # 2 leading blocks
        },
    )
    assert router._pick_prefix(digests) is b
    # And _pick routes through it.
    assert router._pick("px:deadbeef", digests) is b


def test_pick_prefix_miss_falls_back_to_pow2():
    a, b = _FakeReplica("a" * 12), _FakeReplica("b" * 12)
    router = _router([a, b], state={"a" * 12: _adv([999])})
    assert router._pick_prefix([1, 2, 3]) is None
    # _pick still returns a live replica (pure pow-2 on load).
    assert router._pick("", [1, 2, 3]) in (a, b)
    # No digests at all (e.g. non-LLM deployment): same story.
    assert router._pick("") in (a, b)


def test_pick_prefix_saturated_replica_spills():
    a, b = _FakeReplica("a" * 12), _FakeReplica("b" * 12)
    digests = [7]
    state = {"a" * 12: _adv([7])}
    # Hot replica within the margin: sticks.
    router = _router([a, b], state=state, inflight={"a" * 12: 2, "b" * 12: 0})
    assert router._pick_prefix(digests) is a
    # Past the margin: spills to load-only choice.
    router = _router([a, b], state=state, inflight={"a" * 12: 9, "b" * 12: 0})
    assert router._pick_prefix(digests) is None
    assert router._pick("", digests) is b  # pow-2 picks the idle one


def test_prefix_routing_kill_switch():
    a, b = _FakeReplica("a" * 12), _FakeReplica("b" * 12)
    router = _router([a, b], state={"a" * 12: _adv([7])})
    assert router._prefix_routing_on()
    old = GLOBAL_CONFIG.prefix_routing
    GLOBAL_CONFIG.prefix_routing = False
    try:
        assert not router._prefix_routing_on()
    finally:
        GLOBAL_CONFIG.prefix_routing = old


def test_affinity_lists_pruned_on_table_refresh():
    """Satellite: _model_replicas never accumulates dead replica ids —
    table refreshes drop dead members, and an observed death drops them
    immediately."""
    a, b = _FakeReplica("a" * 12), _FakeReplica("b" * 12)
    router = _router([a, b])
    router._model_replicas = {
        "px:k1": ["a" * 12, "dead1"],
        "px:k2": ["dead1", "dead2"],
        "m:model": ["b" * 12],
    }
    router._apply(
        {"version": 2, "replicas": [a, b], "affinity": "prompt_prefix"}
    )
    assert router._model_replicas == {
        "px:k1": ["a" * 12],
        "m:model": ["b" * 12],
    }
    # Observed death: pruned without waiting for a table refresh.
    router._forget_replica("a" * 12)
    assert "px:k1" not in router._model_replicas
    assert router._model_replicas == {"m:model": ["b" * 12]}


def test_router_prefix_counters_in_catalog():
    from ray_tpu.util.metrics import runtime_catalog

    cat = runtime_catalog()
    assert "raytpu_serve_prefix_route_hits_total" in cat
    assert "raytpu_serve_prefix_route_misses_total" in cat
    assert cat["raytpu_serve_prefix_route_hits_total"]["kind"] == "counter"


# -- end-to-end routing ------------------------------------------------------


@pytest.fixture(scope="module")
def cluster():
    import ray_tpu

    runtime = ray_tpu.init(num_cpus=8)
    yield runtime
    from ray_tpu import serve

    try:
        serve.shutdown()
    except Exception:
        pass
    ray_tpu.shutdown()


def _counter(name, deployment):
    from ray_tpu.util.metrics import registry

    for n, tags, v in registry().snapshot()["points"]:
        if n == name and tags.get("deployment") == deployment:
            return v
    return 0.0


def test_shared_prefix_requests_converge_e2e(cluster):
    """Shared-prefix traffic converges on ONE replica: after the first
    request pools the prefix and the advertisement propagates, every
    follow-up routes to that replica (route-hit counter rises) and the
    other replica never prefills the shared blocks (zero prefill tokens
    end to end)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm.serve_llm import build_openai_app

    config = _tiny_config(prefill_chunk_tokens=32)
    h = serve.run(build_openai_app(config, name="pxllm", num_replicas=2))
    try:
        shared = "SYSTEM: you are a helpful assistant, be brief. Q: "

        def ask(suffix):
            return h.remote(
                {
                    "path": "/pxllm/v1/completions",
                    "body": {"prompt": shared + suffix, "max_tokens": 3},
                }
            ).result(timeout=120)

        assert ask("warmup")["object"] == "text_completion"
        ctrl = ray_tpu.get_actor("serve::controller")

        def advertised():
            st = ray_tpu.get(
                ctrl.get_router_state.remote("pxllm"), timeout=30
            )
            return any(
                ((info.get("state") or {}).get("digests"))
                for info in st.values()
            )

        wait_for_condition(advertised, timeout=30, interval=0.5)
        # Let the router's staleness window lapse so its next request
        # fetches the advertised table.
        time.sleep(GLOBAL_CONFIG.prefix_route_staleness_s + 0.5)
        hits0 = _counter("raytpu_serve_prefix_route_hits_total", "pxllm")

        def routed_hit():
            ask("probe")
            return (
                _counter("raytpu_serve_prefix_route_hits_total", "pxllm")
                > hits0
            )

        # The background fetch lands within a couple of requests.
        wait_for_condition(routed_hit, timeout=30, interval=0.2)
        hits1 = _counter("raytpu_serve_prefix_route_hits_total", "pxllm")

        # Zero re-prefill of the shared blocks, measured as DELTAS from a
        # quiescent point (pow-2 probes BEFORE the advertisement landed
        # may legitimately have warmed both replicas): after convergence,
        # every ask pays suffix-only prefill on ONE replica and the other
        # stays frozen.
        def prefill_map():
            st = ray_tpu.get(
                ctrl.get_router_state.remote("pxllm"), timeout=30
            )
            return {
                rid: (info.get("state") or {}).get("prefill_tokens", 0)
                for rid, info in st.items()
            }

        def stable_state():
            s1 = prefill_map()
            time.sleep(1.6)
            return s1 if prefill_map() == s1 else None

        split0 = wait_for_condition(stable_state, timeout=40, interval=0.2)
        for i in range(4):
            ask(f"question {i}")
        assert (
            _counter("raytpu_serve_prefix_route_hits_total", "pxllm")
            >= hits1 + 4
        )

        def converged_deltas():
            cur = prefill_map()
            deltas = [cur.get(r, 0) - split0.get(r, 0) for r in cur]
            pos = [d for d in deltas if d > 0]
            # One replica paid (suffix-only: far below 4 full prompts of
            # ~64 tokens each), the other paid NOTHING.
            return (
                len(deltas) == 2
                and len(pos) == 1
                and 0 < pos[0] <= 4 * 32
                and min(deltas) == 0
            )

        wait_for_condition(converged_deltas, timeout=20, interval=0.5)
    finally:
        serve.delete("pxllm")


def test_kill_switch_restores_pow2_e2e(cluster):
    """RAY_TPU_PREFIX_ROUTING=0: the router never consults digests or
    fetches replica state — the old pow-2 + local-affinity path runs
    untouched (counters frozen, state table stays empty). Uses a plain
    echo deployment declaring the prompt_prefix contract: the kill
    switch is router-side, no engine needed."""
    from ray_tpu import serve

    @serve.deployment(
        name="pxoff",
        num_replicas=2,
        request_affinity="prompt_prefix",
        request_affinity_config={"scheme": BYTE_BOS_SCHEME, "chunk": 16},
    )
    class Echo:
        def __call__(self, request):
            return {"ok": True}

    old = GLOBAL_CONFIG.prefix_routing
    GLOBAL_CONFIG.prefix_routing = False
    h = serve.run(Echo.bind())
    try:
        shared = "SYSTEM: shared system prompt for the kill switch. Q: "
        for i in range(6):
            out = h.remote(
                {"body": {"prompt": shared + str(i)}}
            ).result(timeout=60)
            assert out == {"ok": True}
        assert _counter("raytpu_serve_prefix_route_hits_total", "pxoff") == 0
        assert (
            _counter("raytpu_serve_prefix_route_misses_total", "pxoff") == 0
        )
        from ray_tpu.serve.handle import _routers

        router = _routers.get("pxoff")
        assert router is not None
        assert router._replica_state == {}  # no state fetch ever fired
        assert router._state_task is None

        # Flip the switch back ON (same router, same table): digests are
        # consulted again immediately — the A/B really is one flag flip.
        GLOBAL_CONFIG.prefix_routing = True
        h.remote({"body": {"prompt": shared + "tail"}}).result(timeout=60)
        assert (
            _counter("raytpu_serve_prefix_route_hits_total", "pxoff")
            + _counter("raytpu_serve_prefix_route_misses_total", "pxoff")
            >= 1
        )
    finally:
        GLOBAL_CONFIG.prefix_routing = old
        serve.delete("pxoff")
