"""From a configuration's file (published key names) to the program's own
configuration objects. The only place that knows both vocabularies."""

from __future__ import annotations


def llama_config(c: dict, max_seq: int):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(
        vocab_size=c["vocab_size"],
        n_layer=c["num_hidden_layers"],
        n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"],
        d_model=c["hidden_size"],
        d_ff=c["intermediate_size"],
        max_seq=max_seq,
        rope_theta=c["rope_theta"],
        dtype=jnp.dtype(c["dtype"]),
        param_dtype=jnp.dtype(c["param_dtype"]),
        rms_eps=c["rms_norm_eps"],
    )
    assert cfg.head_dim == c["head_dim"], (cfg.head_dim, c["head_dim"])
    assert c["sliding_window"] is None and not c["tie_word_embeddings"]
    return cfg


def gpt2_config(c: dict, job: dict):
    import jax.numpy as jnp

    from ray_tpu.models.gpt2 import GPT2Config

    d = c["n_embd"]
    assert job["seq_len"] <= c["n_positions"]
    return GPT2Config(
        vocab_size=c["assumed"]["padded_vocab_size"],
        n_layer=c["n_layer"],
        n_head=c["n_head"],
        d_model=d,
        d_ff=c.get("n_inner") or 4 * d,
        max_seq=c["n_positions"],
        dtype=jnp.dtype(c["dtype"]),
        param_dtype=jnp.dtype(c["param_dtype"]),
        attn_impl=job["attn_impl"],
        remat=job["remat"],
        loss_chunk=job["loss_chunk"],
    )


def llm_config(c: dict, mix: dict, seed: int, num_tpus: int = 1):
    """The ``LLMConfig`` one replica of a serve cell runs with."""
    from ray_tpu.llm.config import LLMConfig

    e = mix["engine"]
    return LLMConfig(
        model_id=c["name"],
        model_config=llama_config(c, e["max_seq"]),
        max_slots=e["max_slots"],
        max_seq=e["max_seq"],
        prefill_buckets=tuple(e["prefill_buckets"]),
        kv_block_size=e["kv_block_size"],
        num_kv_blocks=e["num_kv_blocks"],
        placement={"num_tpus": num_tpus, "num_cpus": 1},
        seed=seed,
    )
