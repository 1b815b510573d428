"""Operations and bytes that the algorithm needs, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can move them.
"Needs" means the published mathematics: causal attention counts only the
keys at or before each query, recomputation under ``remat`` counts nothing,
and a decode step needs each weight and each live cache row once, whatever
the program actually moves. Configurations are the JSON objects under
``benchmarks/configs/`` (published key names).
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


# -- Llama family (Mistral) -----------------------------------------------------


def llama_layer_matmul_params(c: dict) -> int:
    """Weights of one block that take part in a matrix multiplication."""
    d, f = c["hidden_size"], c["intermediate_size"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    q = c["num_attention_heads"] * c["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * f


def llama_weight_bytes(c: dict) -> int:
    """Bytes a decode step must read: every block, both norms of each, the
    final norm and the output head. The embedding table is a gather of a few
    rows and is left out."""
    d, L, v = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    n = L * (llama_layer_matmul_params(c) + 2 * d) + d + d * v
    return n * _BYTES[c["param_dtype"]]


def llama_kv_bytes_per_token(c: dict) -> int:
    """Key and value of one position, all layers."""
    kv = c["num_key_value_heads"] * c["head_dim"]
    return 2 * c["num_hidden_layers"] * kv * _BYTES[c["dtype"]]


def llama_decode_step(c: dict, batch: float, context_tokens: float):
    """(operations, bytes) of one decode step over ``batch`` sequences whose
    contexts hold ``context_tokens`` positions together."""
    d, L, v = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    matmul = 2 * batch * (L * llama_layer_matmul_params(c) + d * v)
    attn = 2 * 2 * q * L * context_tokens  # QK^T and PV against live rows
    nbytes = llama_weight_bytes(c) + llama_kv_bytes_per_token(c) * (
        context_tokens + batch  # read the context, write one new position
    )
    return matmul + attn, nbytes


def llama_prefill(c: dict, tokens: int):
    """(operations, bytes) of prefilling one fresh prompt of ``tokens``:
    the head runs on the last position only."""
    d, L, v = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    matmul = 2 * tokens * L * llama_layer_matmul_params(c) + 2 * d * v
    attn = 2 * 2 * q * L * tokens * (tokens + 1) / 2  # causal
    nbytes = llama_weight_bytes(c) + llama_kv_bytes_per_token(c) * tokens
    return matmul + attn, nbytes


# -- GPT-2 ------------------------------------------------------------------------


def gpt2_layer_matmul_params(c: dict) -> int:
    d = c["n_embd"]
    f = c.get("n_inner") or 4 * d
    return d * 3 * d + d * d + 2 * d * f


def gpt2_train_flops_per_token(c: dict, seq_len: int, vocab_rows: int) -> float:
    """Forward and backward operations one training token requires: three
    times the forward pass (two for the backward), recomputation not counted.
    The tied head multiplies by all ``vocab_rows`` rows held (padding included,
    since its logits enter the softmax)."""
    d, L = c["n_embd"], c["n_layer"]
    matmul = 2 * (L * gpt2_layer_matmul_params(c) + vocab_rows * d)
    # Causal attention: a query at position i sees i + 1 keys; the mean over
    # a sequence is (S + 1) / 2. QK^T and PV each cost 2 * d per key.
    attn = L * 2 * 2 * d * (seq_len + 1) / 2
    return 3 * (matmul + attn)


def gpt2_num_params(c: dict, vocab_rows: int) -> int:
    d, L = c["n_embd"], c["n_layer"]
    f = c.get("n_inner") or 4 * d
    per_layer = gpt2_layer_matmul_params(c) + 4 * d + 3 * d + d + f + d
    return vocab_rows * d + c["n_positions"] * d + L * per_layer + 2 * d


# -- shares -----------------------------------------------------------------------


def roofline_pct(ops: float, nbytes: float, seconds: float, peak: dict):
    """(share in %, which bound) of the least time the chip could take."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    bound = "memory" if t_mem >= t_ops else "compute"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
