"""LLM tier configuration.

Reference parity: LLMConfig with TP/placement-group config
(python/ray/llm/_internal/serve/engines/vllm/vllm_models.py:89), minus the
vLLM passthrough fields — parallelism here is a mesh axis, not an engine
flag.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional


@dataclasses.dataclass
class SamplingParams:
    max_tokens: int = 32
    temperature: float = 0.0  # 0 -> greedy
    stop_token: Optional[int] = None
    seed: int = 0


@dataclasses.dataclass
class LLMConfig:
    model_id: str = "gpt2-125m"
    # None -> GPT2Config.gpt2_125m(); tests pass a tiny config.
    model_config: Any = None
    # Serving shape
    # Concurrent sequences (continuous-batching slots). For a family that
    # keeps a recurrent state per sequence (models/paged.py, "What a pool
    # is now") it also sizes that state: max_slots + 1 rows of it a layer.
    max_slots: int = 16
    max_seq: int = 2048  # cache length (prompt + generation)
    prefill_buckets: tuple = (32, 64, 128, 256, 512, 1024, 2048)
    # The KV cache (reference: the block/gpu-memory knobs vLLM exposes,
    # vllm_models.py:89): requests hold block tables of kv_block_size
    # tokens a block over a shared HBM pool of num_kv_blocks; admission
    # reserves ceil(min(prompt+max_tokens, max_seq)/block) blocks, so
    # short requests do not pay max_seq-sized rows. It is the engine's one
    # cache: kv_block_size must be positive and divide max_seq.
    # num_kv_blocks None -> half of max_slots x max_seq positions (2x
    # oversubscription), floored at one max-length request + 1.
    # What a block holds is the family's business: keys and values per
    # head, or latent rows (one [kv_lora_rank + rope] row a token, no head
    # axis), with or without a state per slot that no block holds. Latent
    # rows alone are shared by prefix and prefilled in chunks like keys
    # and values; a family with a state per slot is served without the
    # prefix cache. Either brings programs of its own, and is served
    # without speculative decoding, tensor parallelism and the
    # disaggregated handoff: the engine says so by name at construction.
    # A family whose layers are of two kinds (some attend a sliding window
    # only) has a second part of the pool for the window layers and a second
    # block table a slot. num_kv_blocks keeps meaning the blocks of the
    # layers that keep every position, and admission reserves those as
    # above. A block of a window layer is kv_block_size positions of those
    # layers' keys and values; it is taken just before the program that
    # writes its first position and given back to the free list, while the
    # request runs, once no query can see it (a chunk's blocks at a time
    # during prefill, one every kv_block_size decoded tokens). No setting
    # sizes that part: a slot holds at most ceil((window + the longest
    # prefill program's tokens: prefill_chunk_tokens, or the largest
    # bucket without chunks) / kv_block_size) + 1 of them, and the engine
    # makes max_slots times that and the scratch block. Such a family is
    # served without the prefix cache too (the blocks behind the window at
    # a prefix's end are gone).
    kv_block_size: int = 16
    num_kv_blocks: Optional[int] = None
    # Parallelism: tensor-parallel degree (mesh `tp` axis over local devices)
    tensor_parallelism: int = 1
    # Placement: resources each replica actor demands
    placement: dict = dataclasses.field(
        default_factory=lambda: {"num_cpus": 1}
    )
    # Initial weights: a path to a pickled params pytree, or None for
    # random init (tests; real deployments restore a checkpoint).
    weights_path: Optional[str] = None
    seed: int = 0
    # Prefix caching (reference: vLLM paged-KV prefix reuse +
    # serve prefix-aware routing): chunk-aligned prompt prefixes keep
    # their KV in an HBM pool; a shared system prompt prefills once.
    enable_prefix_caching: bool = True
    prefix_chunk: int = 32  # alignment granularity (tokens)
    max_prefix_cache_tokens: int = 4096  # pool HBM budget, LRU-evicted
    # Chunked prefill (reference: vLLM --enable-chunked-prefill / the
    # Sarathi-style prefill/decode interleave): prompts whose un-cached
    # suffix exceeds this many tokens prefill in chunks of this size, one
    # chunk per engine step, so one long prompt shares steps with in-flight
    # decoders instead of stalling a whole slot-batch for its full prefill
    # (bounds p99 ITL under mixed-length traffic). 0 = disabled (the whole
    # suffix prefills at admission — the pre-round-12 behavior and the
    # kill-switch arm of the A/B). Must be a multiple of kv_block_size,
    # as prefix_chunk must.
    prefill_chunk_tokens: int = 0
    # Speculative decoding (reference: the draft/target scheme vLLM runs
    # under ray.llm; the Gemma-on-TPU serving playbook in PAPERS.md): a
    # small draft model proposes up to this many greedy tokens per engine
    # step and the target model verifies them in ONE multi-token forward
    # (models.paged.paged_verify) — each step then yields
    # 1..k+1 tokens instead of exactly 1, at one target forward per step.
    # Greedy outputs are token-identical to vanilla decode (CI-pinned).
    # 0 = off. RAY_TPU_SPEC_DECODE=0 is the cluster kill switch.
    spec_decode_tokens: int = 0
    # Draft model for speculative decoding: a model config (same families
    # as model_config) whose vocab matches the target's. The draft SHARES
    # the paged pool's block structure — same BlockManager, same block
    # tables — through a parallel {"k","v"} pytree sized by its own
    # layer/head dims. Required when spec_decode_tokens > 0.
    draft_model_config: Any = None
    # Initial draft weights: a path to a pickled params pytree for the
    # draft model (same contract as weights_path for the target), or None
    # for random init. Random init keeps tests hermetic but makes the
    # accept-rate gauge meaningless (a random draft agrees with the
    # target only by chance) — real deployments restore a trained/
    # distilled draft checkpoint here so raytpu_llm_spec_accept_rate
    # reads as actual speculation quality.
    draft_weights_path: Optional[str] = None

    def build_model_config(self):
        from ray_tpu.models.gpt2 import GPT2Config

        if self.model_config is not None:
            return self.model_config
        cfg = GPT2Config.gpt2_125m()
        return dataclasses.replace(cfg, max_seq=max(cfg.max_seq, self.max_seq))
