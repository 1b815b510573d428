"""The Granite 4.0-H family (``granitemoehybrid``: Mamba-2 layers of one group
with a state per session, one grouped-query attention layer of 64-wide heads
in ten without rotation, a SwiGLU behind every mixer, four published
multipliers, a tied head; dense, held whole) as the benchmark reaches it:
served through the paged engine. Configurations use the published key names.
The plain reference is ``reference/granitemoehybrid_ref.py``.

Provides ``model_config``, ``check``, ``shrink``, ``init_params`` and what a
serving family owes the roofline readers: ``decode_step``, ``prefill``,
``attention_decode``, ``weight_bytes``, ``kv_bytes_per_token``,
``state_bytes_per_slot`` (see README.md, "A family").
"""

from __future__ import annotations

import functools

from benchmarks.flops_bytes import BYTES

DECODE_STEPS = 3
SHORT_PROMPT = 77  # beside one of the mix's own lengths: two buckets, off any boundary
# (tokens, answer's length) of the requests that run before the compared two:
# the first and the last leave their slots, with a state in them, and their
# blocks to the two; the second stays and shares their steps.
CHURN = ((100, 2), (120, DECODE_STEPS + 8), (90, 3))
REFERENCE_ALONE = ("fp8", "bf16", "sqrt_scale", "no_residual_multiplier", "no_embedding_multiplier",
                   "unscaled_logits", "untied", "eight_groups")  # the reference computed so
CACHE_WRONGED = ("displaced", "swapped_tables", "stale_state")  # the program, its cache wronged
KV_ROWS = 32  # of each compared request, the newest: the decode steps' and the prompt's last


def model_config(c: dict, traffic: dict):
    import jax.numpy as jnp

    from ray_tpu.models.granite_hybrid import GraniteHybridConfig

    # What the published file says that the program has one way of doing.
    assert not (c["attention_bias"] or c["mamba_proj_bias"]) and c["mamba_conv_bias"]
    assert c["hidden_act"] == "silu" and c["normalization_function"] == "rmsnorm"
    assert c["position_embedding_type"] == "nope" and c["tie_word_embeddings"]
    assert c["num_local_experts"] == c["num_experts_per_tok"] == 0
    assert c["mamba_expand"] * c["hidden_size"] == c["mamba_n_heads"] * c["mamba_d_head"]
    assert len(c["layer_types"]) == c["num_hidden_layers"]
    return GraniteHybridConfig(
        vocab_size=c["vocab_size"],
        d_model=c["hidden_size"],
        layer_types=tuple(c["layer_types"]),
        mamba_heads=c["mamba_n_heads"],
        mamba_head_dim=c["mamba_d_head"],
        ssm_groups=c["mamba_n_groups"],
        ssm_state=c["mamba_d_state"],
        conv_kernel=c["mamba_d_conv"],
        n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        attention_multiplier=float(c["attention_multiplier"]),
        d_ff=c["shared_intermediate_size"],
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        logits_scaling=float(c["logits_scaling"]),
        max_seq=traffic["engine"]["max_seq"],
        state_slots=traffic["engine"]["max_slots"],
        rms_eps=c["rms_norm_eps"],
        dtype=jnp.dtype(c["dtype"]),
        param_dtype=jnp.dtype(c["param_dtype"]),
    )


def init_params(key, cfg):
    from ray_tpu.models import granite_hybrid

    return granite_hybrid.init_params(key, cfg)


def shrink(c: dict) -> dict:
    """The tiny keys of a CPU rehearsal: two turns of a period of four with the
    attention layer inside it, one group, the four multipliers as published."""
    types = ["mamba", "mamba", "attention", "mamba"] * 2
    return {
        **c, "hidden_size": 64, "mamba_expand": 2, "mamba_n_heads": 8, "mamba_d_head": 16,
        "mamba_d_state": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
        "attention_multiplier": 1 / 16, "shared_intermediate_size": 96, "intermediate_size": 96,
        "layer_types": types, "num_hidden_layers": len(types), "vocab_size": 512,
    }


def check(c: dict, traffic: dict, seed: int, who: str, devices=None) -> dict:
    """``program`` is what the cell times: an ``LLMEngine`` built as the
    replica builds it (the mix's settings, the weights its initialiser draws
    from the seed), driven by ``add_request`` and ``step``. Three requests run
    first (``CHURN``); then one prompt of a length from the mix's own table and
    one of 77 tokens are admitted into the slots and blocks the churn left, and
    prefilled and decoded three steps beside the request that stayed. Three
    numbers against the reference's full forward over the same weights:

    - ``logits_rel_err``: the logits the engine samples from (the next token
      is forced on it where it would sample);
    - ``state_rel_err``: each of the two slots' recurrent state ``[Mamba
      layers, H, P, N]`` and convolution tail as they lie in the pool
      afterwards, against the reference's token-by-token state after as many
      tokens (the larger of the two parts' errors: their scales differ);
    - ``kv_rel_err``: the newest ``KV_ROWS`` rows of keys and values of each
      of the two sequences, gathered through the block table the request was
      given: where they were written.

    ``fp8`` and ``bf16`` (every matmul operand rounded so) and the reference's
    own ``wrong`` departures (``sqrt_scale``, ``no_residual_multiplier``,
    ``no_embedding_multiplier``, ``unscaled_logits``, ``untied``,
    ``eight_groups``) put the reference computed that way in the program's
    place, over the weights the engine would draw. The other controls are the
    program with its cache wronged after the first decode step: ``displaced``
    (block tables shifted by one entry), ``swapped_tables`` (the two requests'
    block tables exchanged), ``stale_state`` (their recurrent states and tails
    exchanged)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import model_build
    from benchmarks.reference import granitemoehybrid_ref as ref_mod
    from benchmarks.reference.common import rel_err
    from ray_tpu.llm import LLMEngine, SamplingParams
    from ray_tpu.models import granite_hybrid

    if who not in ("program", *REFERENCE_ALONE, *CACHE_WRONGED):
        raise SystemExit(f"unknown --who {who!r}")
    K = DECODE_STEPS
    rng = np.random.default_rng(seed)
    longest = max(traffic["engine"]["prefill_buckets"]) - K - 1
    lens = [min(int(rng.choice(traffic["prompt_tokens"])), longest), min(SHORT_PROMPT, longest)]
    # as wide as the mix's longest prompt whichever was drawn: one shape of the reference for every seed
    width = min(max(traffic["prompt_tokens"] + [SHORT_PROMPT]), longest) + K
    tokens = rng.integers(0, c["vocab_size"], size=(len(lens), width)).astype(np.int32)
    newest = [slice(max(n + K - KV_ROWS, 0), n + K) for n in lens]
    ended = [n + K for n in lens]  # tokens each sequence's state has taken in by the end
    # the last prompt position and the K after it: the head runs on these alone
    at = np.asarray([np.arange(n - 1, n + K) for n in lens])

    def newest_of(kv):  # [attention layers, sequences, positions, 2 KH Dh] -> the compared rows
        return jnp.concatenate([kv[:, i, rows] for i, rows in enumerate(newest)], axis=1)

    def state_err(state, conv, inner):
        return max(rel_err(state, inner["state"]), rel_err(conv, inner["conv"]))

    forward = functools.partial(ref_mod.forward, c=c, inner=True, keep_at=ended, logits_at=at)
    ref = jax.jit(forward)
    llm_config = model_build.llm_config(c, traffic, seed)
    if who in REFERENCE_ALONE:
        weights = granite_hybrid.init_params(jax.random.key(llm_config.seed), llm_config.model_config)
        how = {"quant": who} if who in ("fp8", "bf16") else {"wrong": who}
        got, got_inner = jax.jit(functools.partial(forward, **how))(weights, jnp.asarray(tokens))
        want, inner = ref(weights, jnp.asarray(tokens))
        return {
            "logits_rel_err": rel_err(got, want),
            "state_rel_err": state_err(got_inner["state"], got_inner["conv"], inner),
            "kv_rel_err": rel_err(newest_of(got_inner["kv"]), newest_of(inner["kv"])),
        }

    engine = LLMEngine(llm_config)
    want, inner = ref(engine.params, jnp.asarray(tokens))
    seen: dict = {f"r{i}": [] for i in range(len(lens))}

    def forced(logits, req):  # where the engine would sample: note the logits, force the token
        rows = seen.get(req.request_id)
        if rows is None:
            return 1  # a churn request: any token that is not its stop token
        rows.append(np.array(logits))
        i, j = int(req.request_id[1:]), len(rows) - 1
        return int(tokens[i, lens[i] + j]) if j < K else 0

    engine._sample = forced
    never = -1  # no token stops a request: each runs its max_tokens
    for n, (length, answer) in enumerate(CHURN):
        engine.add_request(
            f"churn{n}", rng.integers(0, c["vocab_size"], size=min(length, longest)).tolist(),
            SamplingParams(max_tokens=answer, stop_token=never),
        )
    while not (engine.requests["churn0"].finished and engine.requests["churn2"].finished):
        engine.step()
    for i, n in enumerate(lens):
        engine.add_request(f"r{i}", tokens[i, :n].tolist(), SamplingParams(max_tokens=K + 1, stop_token=never))
    engine.step()  # both prefills, then the first decode step of the three
    a, b = (engine.requests[r].slot for r in seen)
    assert min(a, b) >= 0 and engine.stats["state_resets"] == len(CHURN) + len(lens)
    given = engine.block_tables[[a, b]].copy()  # the two requests' tables, before any is wronged
    if who == "displaced":
        engine.block_tables[:] = np.roll(engine.block_tables, 1, axis=1)
    elif who == "swapped_tables":
        engine.block_tables[[a, b]] = engine.block_tables[[b, a]]
    elif who == "stale_state":
        # A row at a time into the donated part, in place: one program that exchanges two rows of the
        # 4.9 GB of state makes a copy of it (the compiler's, on a described v5e), which does not fit
        # beside the engine.
        get_row = jax.jit(lambda x, i: x[:, i])
        set_row = jax.jit(lambda x, row, i: x.at[:, i].set(row), donate_argnums=0)
        for part in ("state", "conv"):
            x = engine.pool.pop(part)
            row_a, row_b = get_row(x, a), get_row(x, b)
            engine.pool[part] = set_row(set_row(x, row_b, a), row_a, b)
    while not all(engine.requests[r].finished for r in seen):
        engine.step()
    got = jnp.stack([jnp.stack([jnp.asarray(x) for x in rows]) for rows in seen.values()])
    out = {"logits_rel_err": rel_err(got, want)}
    slots = jnp.asarray([a, b])
    out["state_rel_err"] = state_err(
        engine.pool["state"][:, slots],
        engine.pool["conv"][:, slots].astype(jnp.float32).reshape(inner["conv"].shape), inner,
    )  # a slot's tail lies in the pool as one flat row
    # [attention layers, blocks, KH, block, v | k]; only the two tables' blocks leave the device
    Dh = c["hidden_size"] // c["num_attention_heads"]
    lie = []
    for i, rows in enumerate(newest):
        kv = np.asarray(engine.pool["kv"][:, given[i]].astype(jnp.float32)).transpose(0, 1, 3, 2, 4)
        kv = kv.reshape(kv.shape[0], -1, kv.shape[3], 2 * Dh)[:, rows]  # [layers, rows, KH, v | k]
        flat = lambda x: x.reshape(*x.shape[:2], -1)  # noqa: E731
        lie.append(np.concatenate([flat(kv[..., Dh:]), flat(kv[..., :Dh])], axis=-1))  # [k; v], the reference's order
    out["kv_rel_err"] = rel_err(jnp.concatenate(lie, axis=1), newest_of(inner["kv"]))
    return out


# -- operations and bytes that the algorithm needs (flops_bytes.py says what "needs" means)


def _sizes(c: dict) -> dict:
    D, H, P, G, N = (c["hidden_size"], c["mamba_n_heads"], c["mamba_d_head"],
                     c["mamba_n_groups"], c["mamba_d_state"])
    Hq, KH, F = c["num_attention_heads"], c["num_key_value_heads"], c["shared_intermediate_size"]
    Dh = D // Hq
    conv_dim = H * P + 2 * G * N
    return {
        "D": D, "H": H, "P": P, "N": N, "Hq": Hq, "KH": KH, "Dh": Dh, "conv_dim": conv_dim,
        "n_m": c["layer_types"].count("mamba"), "n_a": c["layer_types"].count("attention"),
        # weights that take part in a matrix multiplication, per layer of a kind
        "m_mm": D * (H * P + conv_dim + H) + H * P * D,
        "m_other": (c["mamba_d_conv"] + 1) * conv_dim + H * P,  # convolution and bias, the gated norm
        "m_f32": 3 * H,  # dt_bias, A_log and D, float32
        "a_mm": D * Hq * Dh + 2 * D * KH * Dh + Hq * Dh * D,
        "mlp_mm": 3 * D * F,
    }


def num_params(c: dict) -> int:
    """Every parameter of the model, the embedding (which is the head) once."""
    s = _sizes(c)
    layers = c["num_hidden_layers"]
    return (
        s["n_m"] * (s["m_mm"] + s["m_other"] + s["m_f32"]) + s["n_a"] * s["a_mm"]
        + layers * (s["mlp_mm"] + 2 * s["D"]) + s["D"] + s["D"] * c["vocab_size"]
    )


def weight_bytes(c: dict) -> int:
    """Every weight a step reads: all layers, both norms of each, the final
    norm and the embedding once, as the head (its use as a table is a gather
    of a few rows and is left out)."""
    s = _sizes(c)
    f32 = s["n_m"] * s["m_f32"]
    return (num_params(c) - f32) * BYTES[c["param_dtype"]] + f32 * 4


def kv_bytes_per_token(c: dict) -> int:
    """The key and the value of one position, all attention layers: what the
    mathematics needs, whatever the pool pads. (The recurrent state is counted
    by slot in ``decode_step``.)"""
    s = _sizes(c)
    return s["n_a"] * 2 * s["KH"] * s["Dh"] * BYTES[c["dtype"]]


def state_bytes_per_slot(c: dict) -> int:
    """One session's recurrent state (float32) and convolution tails, all
    Mamba layers."""
    s = _sizes(c)
    tails = (c["mamba_d_conv"] - 1) * s["conv_dim"]
    return s["n_m"] * (s["H"] * s["P"] * s["N"] * 4 + tails * BYTES[c["dtype"]])


def state_step_bytes_per_slot(c: dict) -> int:
    """Of :func:`state_bytes_per_slot`, the float32 state alone: what one
    session's state steps of a decode step read, and write again."""
    s = _sizes(c)
    return s["n_m"] * s["H"] * s["P"] * s["N"] * 4


def _token_matmul_ops(c: dict) -> float:
    """Multiply-adds x 2 of one token through every layer's matrices."""
    s = _sizes(c)
    return 2 * (s["n_m"] * s["m_mm"] + s["n_a"] * s["a_mm"] + c["num_hidden_layers"] * s["mlp_mm"])


def _ssm_token_ops(c: dict) -> float:
    """The recurrence of one token, all Mamba layers and heads: decay the
    state (1 a cell), the rank-one write and ``h C`` (2 a cell each)."""
    s = _sizes(c)
    return s["n_m"] * s["H"] * 5 * s["P"] * s["N"]


def attention_decode(c: dict, rows: float, _rows_window: float = 0):
    """(operations, bytes) of one decode step's attention over ``rows`` live
    positions (the sum of ``position + 1`` over the live slots), all attention
    layers: the scores and the values of every query head, each row's key and
    value once at 8,192 B a position as the mathematics needs them, so that a
    padded pool shows as a lower share."""
    s = _sizes(c)
    ops = 2 * s["n_a"] * s["Hq"] * 2 * s["Dh"] * rows
    return ops, kv_bytes_per_token(c) * rows


def decode_step(c: dict, batch: float, context_tokens: float):
    """(operations, bytes) of one decode step over ``batch`` sessions whose
    contexts hold ``context_tokens`` positions together. Bytes: every weight
    and the head once; each live session's state and tails read and written
    once; the live keys and values read and one pair written a session."""
    s = _sizes(c)
    matmul = batch * (_token_matmul_ops(c) + 2 * s["D"] * c["vocab_size"])
    ops = matmul + attention_decode(c, context_tokens)[0] + batch * _ssm_token_ops(c)
    nbytes = (
        weight_bytes(c) + 2 * batch * state_bytes_per_slot(c)
        + kv_bytes_per_token(c) * (context_tokens + batch)
    )
    return ops, nbytes


def prefill(c: dict, tokens: int):
    """(operations, bytes) of prefilling one fresh prompt of ``tokens``: the
    head runs on the last position only; attention is causal; the state is
    written once."""
    s = _sizes(c)
    matmul = tokens * _token_matmul_ops(c) + 2 * s["D"] * c["vocab_size"]
    attn = 2 * s["n_a"] * s["Hq"] * 2 * s["Dh"] * tokens * (tokens + 1) / 2
    ops = matmul + attn + tokens * _ssm_token_ops(c)
    return ops, weight_bytes(c) + kv_bytes_per_token(c) * tokens + state_bytes_per_slot(c)
