"""An LLM deployment is as wide as its engine: build_openai_app deploys
with max_concurrent_queries = LLMConfig.max_slots, so the routing table,
the replica actor's max_concurrency and the execution gate follow the
engine's slot count and not the cluster default serve_max_concurrent
(which every other deployment keeps). Judged by counts, never by time.
"""

import os

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.core.config import GLOBAL_CONFIG
from ray_tpu.llm.config import LLMConfig
from ray_tpu.llm.serve_llm import build_openai_app
from ray_tpu.models.gpt2 import GPT2Config
from ray_tpu.util import trace_export
from ray_tpu.util.state import list_actors


def _tiny_config(max_slots):
    return LLMConfig(
        model_config=GPT2Config.tiny(
            n_layer=2, d_model=64, n_head=2, max_seq=128
        ),
        max_slots=max_slots,
        max_seq=128,
        prefill_buckets=(32, 64, 128),
        # One full reservation a slot, so admission never waits for blocks.
        num_kv_blocks=max_slots * 8 + 1,
        enable_prefix_caching=False,
    )


@pytest.fixture(scope="module")
def cluster():
    runtime = ray_tpu.init(num_cpus=8)
    yield runtime
    try:
        serve.shutdown()
    except Exception:
        pass
    ray_tpu.shutdown()


def _resolved_width(name):
    """(max_concurrent in the routing table, max_concurrency of each live
    replica actor) of one deployment."""
    controller = ray_tpu.get_actor("serve::controller")
    table = ray_tpu.get(controller.get_routing.remote(name, -1), timeout=30)
    actors = [
        a["max_concurrency"]
        for a in list_actors(state="ALIVE")
        if (a["name"] or "").startswith(f"serve::{name}#")
    ]
    return table["max_concurrent"], actors


@pytest.mark.timeout(300)
@pytest.mark.parametrize("max_slots", [4, 12])
def test_llm_deployment_width_is_max_slots(cluster, max_slots):
    """The engine's slot count reaches the routing table and the replica
    actor (two more: they wait in the engine's own queue); a plain
    deployment beside it still gets the cluster default."""

    @serve.deployment
    class Plain:
        def __call__(self, request):
            return {"ok": True}

    assert max_slots != GLOBAL_CONFIG.serve_max_concurrent
    serve.run(build_openai_app(_tiny_config(max_slots), name="wllm"))
    serve.run(Plain.bind())
    try:
        assert _resolved_width("wllm") == (max_slots, [max_slots + 2])
        default = GLOBAL_CONFIG.serve_max_concurrent
        assert _resolved_width("Plain") == (default, [default + 2])
    finally:
        serve.delete("wllm")
        serve.delete("Plain")


@pytest.mark.timeout(300)
def test_llm_replica_fills_every_slot(cluster):
    """max_slots requests sent together all decode in one step: the
    widest ``llm.decode_step`` the replica recorded has max_slots rows.
    (At serve_max_concurrent + 2 = 10 calls in the actor it read 10.)"""
    max_slots, max_tokens = 12, 64
    assert max_slots > GLOBAL_CONFIG.serve_max_concurrent + 2
    h = serve.run(build_openai_app(_tiny_config(max_slots), name="fill"))

    def ask(i):
        return h.remote(
            {
                "path": "/fill/v1/completions",
                "body": {"prompt": f"request {i}:", "max_tokens": max_tokens},
            }
        )

    try:
        ask(0).result(timeout=240)  # both programs compiled
        outs = [r.result(timeout=240) for r in [ask(i) for i in range(max_slots)]]
        # Every request decoded to its limit, so all of them overlapped
        # for as long as the last one to arrive was alive.
        assert [o["usage"]["completion_tokens"] for o in outs] == (
            [max_tokens] * max_slots
        )
        widest = max(
            ev["extra"]["batch"]
            for snap in trace_export.collect_snapshots(
                cluster=True, planes=["llm"]
            )
            # the replica's ring: this process's own holds the steps of
            # whatever engines earlier tests ran in it
            if snap["pid"] != os.getpid()
            for ev in snap["rings"].get("llm", {}).get("events", [])
            if ev["phase"] == "llm.decode_step"
        )
        assert widest == max_slots
    finally:
        serve.delete("fill")
