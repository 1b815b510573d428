"""Disaggregated serving: prefill/decode split over the KV-transfer fabric.

Round-16 tentpole coverage, leg 1: replica roles advertised in the
routing table, router two-hop placement (prefill with prefix-digest bias
→ KV-block handoff over the transfer fabric → decode replica joins the
request mid-decode), the seeded ``kvship`` fault site converging via
local-prefill fallback, and RAY_TPU_DISAGG=0 restoring round-12 unified
serving byte-identically.
"""

import time

import pytest

from conftest import wait_for_condition
from ray_tpu.core import faults
from ray_tpu.core.config import GLOBAL_CONFIG
from ray_tpu.llm.config import LLMConfig, SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models.gpt2 import GPT2Config


def _cfg(**kw):
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


PROMPT = list(range(2, 70))
GREEDY = SamplingParams(max_tokens=10, temperature=0.0)


def _prefill_handoff(engine, prompt, sampling, rid="p"):
    engine.add_request(rid, prompt, sampling, prefill_only=True)
    while engine.has_unfinished():
        engine.step()
    (req,) = engine.pop_finished()
    assert req.finished and req.handoff_out is not None
    return req.handoff_out


# -- engine-level handoff -----------------------------------------------------


def test_two_hop_bit_identical_to_unified():
    """The tentpole contract: prefill on engine A, KV shipped to engine
    B, decode on B — greedy output bit-equal a unified engine C, with B
    paying ZERO prefill tokens (the whole point of the split)."""
    A, B, C = LLMEngine(_cfg()), LLMEngine(_cfg()), LLMEngine(_cfg())
    h = _prefill_handoff(A, PROMPT, GREEDY)
    assert h["prompt"] == PROMPT and not h["finished"]
    assert h["nblocks"] == -(-len(PROMPT) // 16)
    assert A.stats["handoffs_out"] == 1
    B.add_handoff_request("d", h, GREEDY)
    while B.has_unfinished():
        B.step()
    (got,) = B.pop_finished()
    want = C.generate([PROMPT], GREEDY)[0]["token_ids"]
    assert got.generated == want
    assert B.stats["handoffs_in"] == 1
    assert B.stats["kv_fallbacks"] == 0
    assert B.stats["prefill_tokens"] == 0  # decode tier never prefilled
    # The prefill engine released everything: no slots, no stray blocks
    # beyond its (refcounted) prefix pool.
    assert all(A.slot_free)


def test_handoff_finished_at_prefill_ships_no_kv():
    """max_tokens=1: the first token IS the response — the handoff says
    finished, ships no KV, and the decode engine takes no slot."""
    A, B = LLMEngine(_cfg()), LLMEngine(_cfg())
    s = SamplingParams(max_tokens=1, temperature=0.0)
    h = _prefill_handoff(A, PROMPT, s)
    assert h["finished"] and "kv" not in h
    B.add_handoff_request("d", h, s)
    while B.has_unfinished():
        B.step()
    (req,) = B.pop_finished()
    assert req.generated == [h["first_token"]]
    assert B.stats["handoffs_in"] == 0  # nothing pulled
    assert all(B.slot_free)


def test_kv_ship_bytes_counted():
    from ray_tpu.util.metrics import registry

    def shipped():
        total = 0.0
        for n, _t, v in registry().snapshot()["points"]:
            if n == "raytpu_llm_kv_ship_bytes_total":
                total += v
        return total

    before = shipped()
    A, B = LLMEngine(_cfg()), LLMEngine(_cfg())
    h = _prefill_handoff(A, PROMPT, GREEDY)
    B.add_handoff_request("d", h, GREEDY)
    while B.has_unfinished():
        B.step()
    B.pop_finished()
    assert shipped() > before


def test_chunked_prefill_only_exports_same_handoff_tokens():
    """The prefill leg composes with chunked prefill: a prefill-only
    request that chunks its prompt exports the same first token as an
    unchunked one, and the decode side converges identically."""
    A1 = LLMEngine(_cfg())
    A2 = LLMEngine(_cfg(prefill_chunk_tokens=16))
    h1 = _prefill_handoff(A1, PROMPT, GREEDY)
    h2 = _prefill_handoff(A2, PROMPT, GREEDY)
    assert A2.stats["prefill_chunks"] >= 2  # chunking actually ran
    assert h1["first_token"] == h2["first_token"]
    assert h1["nblocks"] == h2["nblocks"]
    B = LLMEngine(_cfg())
    B.add_handoff_request("d", h2, GREEDY)
    while B.has_unfinished():
        B.step()
    want = LLMEngine(_cfg()).generate([PROMPT], GREEDY)[0]["token_ids"]
    assert B.pop_finished()[0].generated == want


def test_handoff_with_spec_decode_on_decode_tier():
    """The two legs compose: a handoff-admitted request speculates on
    the decode engine (draft prefilled locally from the shipped prompt)
    and stays bit-identical to unified vanilla decode."""
    draft = GPT2Config.tiny(n_layer=1, d_model=32, n_head=2, max_seq=256)
    A = LLMEngine(_cfg())
    B = LLMEngine(_cfg(spec_decode_tokens=3, draft_model_config=draft))
    h = _prefill_handoff(A, PROMPT, GREEDY)
    B.add_handoff_request("d", h, GREEDY)
    while B.has_unfinished():
        B.step()
    want = LLMEngine(_cfg()).generate([PROMPT], GREEDY)[0]["token_ids"]
    assert B.pop_finished()[0].generated == want
    assert B.stats["spec_steps"] >= 1
    assert B.stats["prefill_tokens"] == 0  # target never prefilled here


# -- seeded kvship chaos ------------------------------------------------------


def _severed_run(seed: int):
    """One decode-tier run under a seeded kvship sever; returns (tokens,
    stats snapshot) for replay comparison."""
    A = LLMEngine(_cfg())
    B = LLMEngine(_cfg(prefill_chunk_tokens=32))
    h = _prefill_handoff(A, PROMPT, GREEDY)
    faults.install(faults.parse_spec(seed, "kvship.sever"))
    try:
        B.add_handoff_request("d", h, GREEDY)
        steps = 0
        while B.has_unfinished():
            B.step()
            steps += 1
            assert steps < 200  # converges — no hang
        (req,) = B.pop_finished()
    finally:
        faults.clear()
    return req.generated, dict(B.stats)


def test_kvship_sever_falls_back_to_local_chunked_prefill():
    """The acceptance chaos case: a severed mid-transfer handoff makes
    the decode replica fall back to LOCAL chunked prefill — no hang, no
    token divergence, fallback counted — and the seeded schedule replays
    bit-identically."""
    want = LLMEngine(_cfg()).generate([PROMPT], GREEDY)[0]["token_ids"]
    got, stats = _severed_run(7)
    assert got == want  # no token divergence vs unified
    assert stats["kv_fallbacks"] == 1
    assert stats["handoffs_in"] == 0
    assert stats["prefill_chunks"] >= 2  # the fallback really chunked
    assert stats["prefill_tokens"] == len(PROMPT)
    # Bit-identical replay from the same seed.
    got2, stats2 = _severed_run(7)
    assert got2 == got
    assert stats2 == stats


def test_kvship_probabilistic_sever_seeded_replay():
    """p<1 rules draw from the rule's own seeded stream: two runs of the
    same multi-request schedule at the same seed take identical
    fallback-vs-pull decisions; a different seed may diverge (and the
    outputs stay correct either way)."""
    prompts = [list(range(2, 40 + 8 * i)) for i in range(4)]
    want = [
        r["token_ids"]
        for r in LLMEngine(_cfg()).generate(prompts, GREEDY)
    ]

    def run(seed):
        A = LLMEngine(_cfg())
        B = LLMEngine(_cfg(prefill_chunk_tokens=32))
        hs = [
            _prefill_handoff(A, p, GREEDY, rid=f"p{i}")
            for i, p in enumerate(prompts)
        ]
        faults.install(faults.parse_spec(seed, "kvship.sever,p=0.5"))
        try:
            for i, h in enumerate(hs):
                B.add_handoff_request(f"d{i}", h, GREEDY)
            while B.has_unfinished():
                B.step()
            done = {r.request_id: r.generated for r in B.pop_finished()}
        finally:
            faults.clear()
        return [done[f"d{i}"] for i in range(4)], (
            B.stats["kv_fallbacks"], B.stats["handoffs_in"],
        )

    out1, dec1 = run(21)
    out2, dec2 = run(21)
    assert out1 == want and out2 == want
    assert dec1 == dec2  # same seed -> same sever schedule
    assert 0 < dec1[0] < 4  # p=0.5 actually mixed both outcomes


# -- serve tier ---------------------------------------------------------------


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

    total = 0.0
    for n, tags, v in registry().snapshot()["points"]:
        if n == name and tags.get("deployment") == deployment:
            total += v
    return total


def test_controller_strips_roles_under_kill_switch():
    """Controller side of RAY_TPU_DISAGG=0: get_routing's table carries
    no disagg key at all — byte-identical to a unified deployment's
    (the admission plane's strip pattern). Driven on a bare controller:
    the knob is process-local, so the e2e test can only flip its own
    router's half."""
    import asyncio

    from ray_tpu.serve.controller import ServeController

    ctrl = ServeController.__new__(ServeController)
    ctrl._deployments = {
        "d": {
            "config": {
                "num_replicas": 2,
                "disagg_config": {"prefill_replicas": 1},
            },
            "payload": b"",
            "init": b"",
            "replicas": [],
            "version": 3,
            "next_replica_id": 2,
        }
    }

    loop = asyncio.new_event_loop()
    asyncio.set_event_loop(loop)
    try:
        assert "disagg" in loop.run_until_complete(ctrl.get_routing("d", -1))
        old = GLOBAL_CONFIG.disagg
        GLOBAL_CONFIG.disagg = False
        try:
            stripped = loop.run_until_complete(ctrl.get_routing("d", -1))
            assert "disagg" not in stripped
            # And it equals a unified deployment's table key-for-key.
            del ctrl._deployments["d"]["config"]["disagg_config"]
            unified = loop.run_until_complete(ctrl.get_routing("d", -1))
            assert stripped == unified
        finally:
            GLOBAL_CONFIG.disagg = old
    finally:
        loop.close()
        asyncio.set_event_loop(None)


def test_disagg_two_hop_e2e_bit_identical(cluster):
    """Serve e2e: a 1-prefill + 1-decode deployment answers exactly like
    a unified single replica (greedy), handoffs counted once per request,
    and the routing table advertises the roles."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm.serve_llm import build_openai_app

    cfg = _cfg()
    h = serve.run(
        build_openai_app(
            cfg, name="dxllm", num_replicas=1, prefill_replicas=1
        )
    )
    u = serve.run(build_openai_app(cfg, name="uxllm", num_replicas=1))
    try:
        body = {"prompt": "SYSTEM: disagg e2e. Q: alpha", "max_tokens": 8}

        def ask(handle, name):
            return handle.remote(
                {"path": f"/{name}/v1/completions", "body": dict(body)}
            ).result(timeout=120)

        h0 = _counter("raytpu_serve_disagg_handoffs_total", "dxllm")
        out_d = ask(h, "dxllm")
        out_u = ask(u, "uxllm")
        assert out_d["choices"][0]["text"] == out_u["choices"][0]["text"]
        assert (
            _counter("raytpu_serve_disagg_handoffs_total", "dxllm")
            == h0 + 1
        )
        # Roles rode the table.
        ctrl = ray_tpu.get_actor("serve::controller")
        table = ray_tpu.get(
            ctrl.get_routing.remote("dxllm", -1), timeout=30
        )
        roles = table["disagg"]["roles"]
        assert sorted(roles.values()) == ["decode", "prefill"]
        # Streaming rides the same two-hop.
        chunks = list(
            h.options(stream=True).remote(
                {
                    "path": "/dxllm/v1/completions",
                    "body": dict(body, stream=True),
                }
            )
        )
        text = "".join(
            c["choices"][0]["text"]
            for c in chunks
            if c["choices"][0]["text"]
        )
        assert text == out_u["choices"][0]["text"]
        assert (
            _counter("raytpu_serve_disagg_handoffs_total", "dxllm")
            == h0 + 2
        )
    finally:
        serve.delete("dxllm")
        serve.delete("uxllm")


def test_disagg_kill_switch_e2e_one_flag_flip(cluster):
    """RAY_TPU_DISAGG=0: the routing table carries NO disagg key (byte-
    identical to a unified deployment's) and the router never two-hops —
    the counter freezes; flipping back on resumes handoffs with no
    redeploy."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm.serve_llm import build_openai_app

    h = serve.run(
        build_openai_app(
            _cfg(), name="dkllm", num_replicas=1, prefill_replicas=1
        )
    )
    try:

        def ask(i):
            return h.remote(
                {
                    "path": "/dkllm/v1/completions",
                    "body": {"prompt": f"kill switch {i}", "max_tokens": 4},
                }
            ).result(timeout=120)

        ask(0)
        on0 = _counter("raytpu_serve_disagg_handoffs_total", "dkllm")
        assert on0 >= 1
        old = GLOBAL_CONFIG.disagg
        # The knob is per-process: flipping it in the driver disables the
        # two-hop in this driver's routers NOW (cluster-wide, the env var
        # reaches every process at start; the controller-side table strip
        # is pinned by test_controller_strips_roles_under_kill_switch).
        GLOBAL_CONFIG.disagg = False
        try:
            out = ask(1)
            assert out["object"] == "text_completion"
            assert (
                _counter("raytpu_serve_disagg_handoffs_total", "dkllm")
                == on0
            )
        finally:
            GLOBAL_CONFIG.disagg = old
        ask(2)
        assert (
            _counter("raytpu_serve_disagg_handoffs_total", "dkllm") > on0
        )
    finally:
        serve.delete("dkllm")


def test_disagg_decode_tier_survives_prefill_death(cluster):
    """Availability: killing the prefill replica degrades requests to
    unified routing (the decode replica serves them alone, prefilling
    locally) until the controller replaces it — no failed requests."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm.serve_llm import build_openai_app

    h = serve.run(
        build_openai_app(
            _cfg(), name="dfllm", num_replicas=1, prefill_replicas=1
        )
    )
    try:
        def ask(i):
            return h.remote(
                {
                    "path": "/dfllm/v1/completions",
                    "body": {"prompt": f"failover {i}", "max_tokens": 4},
                }
            ).result(timeout=120)

        ask(0)
        ctrl = ray_tpu.get_actor("serve::controller")
        table = ray_tpu.get(ctrl.get_routing.remote("dfllm", -1), timeout=30)
        roles = table["disagg"]["roles"]
        prefill_rid = next(
            rid for rid, role in roles.items() if role == "prefill"
        )
        victim = next(
            r for r in table["replicas"] if r._actor_id == prefill_rid
        )
        ray_tpu.kill(victim)
        # Every request during AND after the replacement window succeeds.
        for i in range(1, 6):
            out = ask(i)
            assert out["object"] == "text_completion"
            time.sleep(0.3)
        # The controller eventually restores a 2-replica role split.
        def healed():
            t = ray_tpu.get(ctrl.get_routing.remote("dfllm", -1), timeout=30)
            roles = (t.get("disagg") or {}).get("roles") or {}
            return sorted(roles.values()) == ["decode", "prefill"]

        wait_for_condition(healed, timeout=60, interval=0.5)
        assert ask(9)["object"] == "text_completion"
    finally:
        serve.delete("dfllm")
