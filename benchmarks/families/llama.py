"""The Llama block family (grouped-query attention, RoPE, SwiGLU) as the
benchmark reaches it: served through the paged engine. Configurations use the
published key names (``hidden_size``, ``num_key_value_heads`` ...); the plain
reference is ``reference/llama_ref.py``.

Provides ``model_config``, ``check``, ``shrink``, ``init_params`` and what a
serving family owes the roofline readers: ``decode_step``, ``prefill``,
``weight_bytes``, ``kv_bytes_per_token`` (see README.md, "A family").
"""

from __future__ import annotations

import functools

from benchmarks.flops_bytes import BYTES

DECODE_STEPS = 3
CHECK_PROMPTS = (200, 77)  # two buckets, two slots, lengths off any boundary


def model_config(c: dict, traffic: dict):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(
        vocab_size=c["vocab_size"],
        n_layer=c["num_hidden_layers"],
        n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"],
        d_model=c["hidden_size"],
        d_ff=c["intermediate_size"],
        max_seq=traffic["engine"]["max_seq"],
        rope_theta=c["rope_theta"],
        dtype=jnp.dtype(c["dtype"]),
        param_dtype=jnp.dtype(c["param_dtype"]),
        rms_eps=c["rms_norm_eps"],
    )
    assert cfg.head_dim == c["head_dim"], (cfg.head_dim, c["head_dim"])
    assert c["sliding_window"] is None and not c["tie_word_embeddings"]
    return cfg


def init_params(key, cfg):
    from ray_tpu.models import llama

    return llama.init_params(key, cfg)


def shrink(c: dict) -> dict:
    """The tiny keys of a CPU rehearsal."""
    return {**c, "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": 2,
            "vocab_size": 512}


def check(c: dict, traffic: dict, seed: int, who: str, devices=None) -> dict:
    """``program`` is the paged prefill (two prompts, two buckets, scattered
    block tables) and three decode steps; ``fp8`` and ``bf16`` put the
    reference computed in that precision in its place; ``displaced`` is the
    program with its block tables shifted by one entry before decoding."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import llama_ref
    from benchmarks.reference.common import rel_err
    from ray_tpu.models import paged

    engine = traffic["engine"]
    bs, S = engine["kv_block_size"], engine["max_seq"]
    B, N, W = engine["max_slots"], engine["num_kv_blocks"], S // bs
    K = DECODE_STEPS
    lens = [min(n, max(engine["prefill_buckets"]) - K - 1) for n in CHECK_PROMPTS]
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, c["vocab_size"], size=(len(lens), max(lens) + K)).astype(np.int32)
    weights = llama_ref.init_weights(seed, c)
    ref = jax.jit(functools.partial(llama_ref.forward, c=c, quant=None))

    def compared(logits):  # the last prompt position and the K after it
        return jnp.concatenate([logits[i, n - 1 : n + K] for i, n in enumerate(lens)])

    want = compared(ref(weights, jnp.asarray(tokens)))
    if who in ("fp8", "bf16"):
        ctl = jax.jit(functools.partial(llama_ref.forward, c=c, quant=who))
        return {"logits_rel_err": rel_err(compared(ctl(weights, jnp.asarray(tokens))), want)}

    cfg = model_config(c, traffic)
    pg_prefill = jax.jit(functools.partial(paged.paged_prefill, cfg=cfg, block_size=bs))
    pg_decode = jax.jit(functools.partial(paged.paged_decode, cfg=cfg, block_size=bs))
    pool = paged.init_block_pool(cfg, N, bs)
    free = list(rng.permutation(np.arange(1, N)))  # scattered, as after churn
    slots = rng.choice(B, size=len(lens), replace=False)
    tables = np.zeros((B, W), np.int32)
    rows = []
    for i, n in enumerate(lens):
        need = -(-(n + K) // bs)
        tables[slots[i], :need] = [free.pop() for _ in range(need)]
        bucket = min(b for b in engine["prefill_buckets"] if b >= n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = tokens[i, :n]
        pool, logits = pg_prefill(
            weights, jnp.asarray(toks), jnp.asarray(n, jnp.int32),
            jnp.asarray(0, jnp.int32), jnp.asarray(tables[slots[i]]), pool,
        )
        rows.append([logits])
    if who == "displaced":
        tables = np.roll(tables, 1, axis=1)
    elif who != "program":
        raise SystemExit(f"unknown --who {who!r}")
    for k in range(K):
        last = np.zeros(B, np.int32)
        pos = np.zeros(B, np.int32)
        for i, n in enumerate(lens):
            last[slots[i]], pos[slots[i]] = tokens[i, n + k], n + k
        pool, logits = pg_decode(
            weights, jnp.asarray(last), jnp.asarray(pos), jnp.asarray(tables), pool
        )
        for i in range(len(lens)):
            rows[i].append(logits[slots[i]])
    got = jnp.stack([x for row in rows for x in row])
    return {"logits_rel_err": rel_err(got, want)}


# -- operations and bytes that the algorithm needs (flops_bytes.py says what "needs" means)


def layer_matmul_params(c: dict) -> int:
    """Weights of one block that take part in a matrix multiplication."""
    d, f = c["hidden_size"], c["intermediate_size"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    q = c["num_attention_heads"] * c["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * f


def weight_bytes(c: dict) -> int:
    """Bytes a decode step must read: every block, both norms of each, the
    final norm and the output head. The embedding table is a gather of a few
    rows and is left out."""
    d, L, v = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    n = L * (layer_matmul_params(c) + 2 * d) + d + d * v
    return n * BYTES[c["param_dtype"]]


def kv_bytes_per_token(c: dict) -> int:
    """Key and value of one position, all layers."""
    kv = c["num_key_value_heads"] * c["head_dim"]
    return 2 * c["num_hidden_layers"] * kv * BYTES[c["dtype"]]


def decode_step(c: dict, batch: float, context_tokens: float):
    """(operations, bytes) of one decode step over ``batch`` sequences whose
    contexts hold ``context_tokens`` positions together."""
    d, L, v = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    matmul = 2 * batch * (L * layer_matmul_params(c) + d * v)
    attn = 2 * 2 * q * L * context_tokens  # QK^T and PV against live rows
    nbytes = weight_bytes(c) + kv_bytes_per_token(c) * (
        context_tokens + batch  # read the context, write one new position
    )
    return matmul + attn, nbytes


def prefill(c: dict, tokens: int):
    """(operations, bytes) of prefilling one fresh prompt of ``tokens``:
    the head runs on the last position only."""
    d, L, v = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    matmul = 2 * tokens * L * layer_matmul_params(c) + 2 * d * v
    attn = 2 * 2 * q * L * tokens * (tokens + 1) / 2  # causal
    nbytes = weight_bytes(c) + kv_bytes_per_token(c) * tokens
    return matmul + attn, nbytes
