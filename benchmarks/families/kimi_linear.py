"""The Kimi Linear family (KDA linear attention with a recurrent state per
sequence, latent attention every fourth layer, routed experts of which this
chip holds its share) as the benchmark reaches it: served through the paged
engine. Configurations use the published key names; ``num_experts`` is the
count of experts held here from ``expert_offset``, ``published.num_experts``
the router's width, and layers 1..``num_hidden_layers`` of the published
``linear_attn_config`` are held. The plain reference is
``reference/kimi_linear_ref.py``.

Provides ``model_config``, ``check``, ``shrink``, ``init_params`` and what a
serving family owes the roofline readers: ``decode_step``, ``prefill``,
``weight_bytes``, ``kv_bytes_per_token`` (see README.md, "A family").
"""

from __future__ import annotations

import functools

from benchmarks.flops_bytes import BYTES

DECODE_STEPS = 3
SHORT_PROMPT = 77  # beside one of the mix's own lengths: two buckets, off any boundary
# (tokens, answer's length) of the requests that run before the compared two:
# the first and the last leave their slots, with a state in them, to the two;
# the second stays and shares their steps.
CHURN = ((100, 2), (120, DECODE_STEPS + 8), (90, 3))
CONTROLS = ("displaced", "swapped_tables", "stale_state")
LATENT_ROWS = 32  # of each compared request, the newest: the decode steps' and the prompt's last


def _held_layers(c: dict) -> tuple:
    la, L = c["linear_attn_config"], c["num_hidden_layers"]
    kda = tuple(i for i in la["kda_layers"] if i <= L)
    mla = tuple(i for i in la["full_attn_layers"] if i <= L)
    return kda, mla


def model_config(c: dict, traffic: dict):
    import jax.numpy as jnp

    from ray_tpu.models.kimi_linear import KimiLinearConfig

    # What the published file says that the program has one way of doing.
    assert c["q_lora_rank"] is None and c["mla_use_nope"] and c["rope_scaling"] is None
    assert c["num_expert_group"] == c["topk_group"] == c["moe_layer_freq"] == 1
    assert c["moe_router_activation_func"] == "sigmoid" and c["hidden_act"] == "silu"
    assert c["num_nextn_predict_layers"] == 0 and not c["tie_word_embeddings"]
    la = c["linear_attn_config"]
    kda, mla = _held_layers(c)
    return KimiLinearConfig(
        vocab_size=c["vocab_size"],
        n_layer=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        kda_layers=kda,
        mla_layers=mla,
        kda_heads=la["num_heads"],
        kda_head_dim=la["head_dim"],
        conv_kernel=la["short_conv_kernel_size"],
        kda_gate_rank=c["assumed"]["kda_gate_rank"],
        n_head=c["num_attention_heads"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"],
        d_ff=c["intermediate_size"],
        first_k_dense=c["first_k_dense_replace"],
        moe_d_ff=c["moe_intermediate_size"],
        n_experts=c["published"]["num_experts"],
        experts_held=c["num_experts"],
        expert_offset=c["expert_offset"],
        experts_per_token=c["num_experts_per_token"],
        n_shared_experts=c["num_shared_experts"],
        routed_scaling=c["routed_scaling_factor"],
        renormalize=c["moe_renormalize"],
        max_seq=traffic["engine"]["max_seq"],
        state_slots=traffic["engine"]["max_slots"],
        rms_eps=c["rms_norm_eps"],
        dtype=jnp.dtype(c["dtype"]),
        param_dtype=jnp.dtype(c["param_dtype"]),
    )


def init_params(key, cfg):
    from ray_tpu.models import kimi_linear

    return kimi_linear.init_params(key, cfg)


def shrink(c: dict) -> dict:
    """The tiny keys of a CPU rehearsal: five layers (dense + KDA first, one
    MLA layer), four of eight experts held."""
    return {
        **c, "hidden_size": 64, "head_dim": 32, "intermediate_size": 128,
        "num_attention_heads": 2, "num_key_value_heads": 2, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "linear_attn_config": {**c["linear_attn_config"], "head_dim": 16, "num_heads": 2},
        "moe_intermediate_size": 32, "num_experts": 4, "num_experts_per_token": 2,
        "num_hidden_layers": 5, "vocab_size": 512,
        "published": {**c["published"], "num_experts": 8},
        "assumed": {**c["assumed"], "kda_gate_rank": 8},
    }


def check(c: dict, traffic: dict, seed: int, who: str, devices=None) -> dict:
    """``program`` is what the cell times: an ``LLMEngine`` built as the
    replica builds it (the mix's settings, the weights its initialiser draws
    from the seed, selection bias balanced), driven by ``add_request`` and
    ``step``. Three requests run first (``CHURN``); then one prompt of a length
    from the mix's own table and one of 77 tokens are admitted into the slots
    and blocks the churn left, and prefilled and decoded three steps beside the
    request that stayed. ``logits_rel_err``: the logits the engine samples
    from, against the reference's full forward over the same weights; the next
    token is forced on the engine where it would sample. ``latent_rel_err``:
    the newest ``LATENT_ROWS`` rows of each of the two sequences as they lie
    in the latent pool afterwards, gathered through the block table the
    request was given, against the reference's ``[c^; k_pe]`` at those
    positions: the latent layers attend without positions, so the logits
    hardly tell which rows were read, and this tells where they were
    written. Also ``route_agree_pct``: the share of the long prompt's (token,
    expert layer, pick) choices on which program and reference agree, so that
    an error raised by flipped near-tie picks is seen for what it is. ``fp8``
    and ``bf16`` put the reference computed in that precision in the
    program's place (logits only). The other controls are the program with
    its cache wronged after the first decode step: ``displaced`` (block tables
    shifted by one entry), ``swapped_tables`` (the two requests' block tables
    exchanged), ``stale_state`` (their recurrent states exchanged)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import model_build
    from benchmarks.reference import kimi_linear_ref
    from benchmarks.reference.common import rel_err
    from ray_tpu.llm import LLMEngine, SamplingParams
    from ray_tpu.models import kimi_linear

    if who not in ("program", "fp8", "bf16", *CONTROLS):
        raise SystemExit(f"unknown --who {who!r}")
    K = DECODE_STEPS
    rng = np.random.default_rng(seed)
    longest = max(traffic["engine"]["prefill_buckets"]) - K - 1
    lens = [min(int(rng.choice(traffic["prompt_tokens"])), longest), min(SHORT_PROMPT, longest)]
    # as wide as the mix's longest prompt whichever was drawn: one shape of the reference for every seed
    width = min(max(traffic["prompt_tokens"] + [SHORT_PROMPT]), longest) + K
    tokens = rng.integers(0, c["vocab_size"], size=(len(lens), width)).astype(np.int32)

    def compared(logits):  # the last prompt position and the K after it
        return jnp.concatenate([logits[i, n - 1 : n + K] for i, n in enumerate(lens)])

    ref = jax.jit(functools.partial(kimi_linear_ref.forward, c=c, quant=None, inner=True))
    llm_config = model_build.llm_config(c, traffic, seed)
    if who in ("fp8", "bf16"):  # the reference alone, over the weights the engine would draw
        weights = kimi_linear.init_params(jax.random.key(llm_config.seed), llm_config.model_config)
        ctl = jax.jit(functools.partial(kimi_linear_ref.forward, c=c, quant=who))
        got = compared(ctl(weights, jnp.asarray(tokens)))
        return {"logits_rel_err": rel_err(got, compared(ref(weights, jnp.asarray(tokens))[0]))}

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
        state = engine.pool["state"]
        engine.pool = {**engine.pool, "state": state.at[:, a].set(state[:, b]).at[:, b].set(state[:, a])}
    while not all(engine.requests[r].finished for r in seen):
        engine.step()
    got = jnp.stack([x for rows in seen.values() for x in rows])
    out = {"logits_rel_err": rel_err(got, compared(want))}
    ckv = np.asarray(engine.pool["ckv"].astype(jnp.float32))  # [MLA layers, blocks, block, 576]
    newest = [slice(max(n + K - LATENT_ROWS, 0), n + K) for n in lens]
    lie = [ckv[:, given[i]].reshape(ckv.shape[0], -1, ckv.shape[-1])[:, rows] for i, rows in enumerate(newest)]
    out["latent_rel_err"] = rel_err(
        jnp.concatenate(lie, axis=1),
        jnp.concatenate([inner["latents"][:, i, rows] for i, rows in enumerate(newest)], axis=1),
    )
    if who == "program":  # the long prompt's prefill once more, for its picks
        cfg, bs = llm_config.model_config, llm_config.kv_block_size
        n = lens[0]
        bucket = min(x for x in llm_config.prefill_buckets if x >= n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = tokens[0, :n]
        blocks = -(-bucket // bs)
        *_, picks = jax.jit(functools.partial(
            kimi_linear.paged_prefill, cfg=cfg, block_size=bs, with_picks=True,
        ))(
            engine.params, jnp.asarray(toks), jnp.asarray(n, jnp.int32), jnp.asarray(0, jnp.int32),
            jnp.arange(1, blocks + 1, dtype=jnp.int32), kimi_linear.init_pool(cfg, blocks + 1, bs, 0),
        )
        same = np.sort(np.asarray(picks[:, :n]), -1) == np.sort(np.asarray(inner["picks"][:, 0, :n]), -1)
        out["route_agree_pct"] = 100.0 * float(same.mean())
    return out


# -- operations and bytes that the algorithm needs (flops_bytes.py says what "needs" means)


def _sizes(c: dict) -> dict:
    la = c["linear_attn_config"]
    kda, mla = _held_layers(c)
    H, d = la["num_heads"], la["head_dim"]
    Hm, dn, dp, dv = (c["num_attention_heads"], c["qk_nope_head_dim"],
                      c["qk_rope_head_dim"], c["v_head_dim"])
    D, r, R = c["hidden_size"], c["assumed"]["kda_gate_rank"], c["kv_lora_rank"]
    dense = c["first_k_dense_replace"]
    return {
        "D": D, "H": H, "d": d, "Hm": Hm, "dn": dn, "dp": dp, "dv": dv, "R": R,
        "n_kda": len(kda), "n_mla": len(mla), "n_dense": dense,
        "n_moe": c["num_hidden_layers"] - dense,
        # weights that take part in a matrix multiplication, per layer of a kind
        "kda_mm": 3 * D * H * d + H * d * D + 2 * (D * r + r * H * d) + D * H,
        "kda_other": la["short_conv_kernel_size"] * 3 * H * d + d,  # convolutions, the output norm
        "kda_f32": H * d + H,  # dt_bias and A_log, float32 like the router
        "mla_mm": D * Hm * (dn + dp) + D * (R + dp) + R * Hm * (dn + dv) + Hm * dv * D,
        "mla_other": R,  # kv_norm
        "dense_mm": 3 * D * c["intermediate_size"],
        "expert_mm": 3 * D * c["moe_intermediate_size"],
        "shared_mm": 3 * D * c["moe_intermediate_size"] * c["num_shared_experts"],
        "router": D * c["published"]["num_experts"],  # float32, with its bias
    }


def non_expert_weight_bytes(c: dict) -> int:
    """Every weight a step reads whatever the routing: mixers, the dense
    layer's MLP, routers (float32) and shared experts, both norms of each
    layer, the final norm and the head over the vocabulary held. The
    embedding table is a gather of a few rows and is left out."""
    s, b = _sizes(c), BYTES[c["param_dtype"]]
    n = (
        s["n_kda"] * (s["kda_mm"] + s["kda_other"]) + s["n_mla"] * (s["mla_mm"] + s["mla_other"])
        + s["n_dense"] * s["dense_mm"] + s["n_moe"] * s["shared_mm"]
        + 2 * s["D"] * c["num_hidden_layers"] + s["D"] + s["D"] * c["vocab_size"]
    )
    f32 = s["n_kda"] * s["kda_f32"] + s["n_moe"] * (s["router"] + c["published"]["num_experts"])
    return n * b + f32 * 4


def weight_bytes(c: dict) -> int:
    """All weights held here but the embedding table: what a prefill reads,
    whose tokens reach every expert held."""
    s = _sizes(c)
    experts = s["n_moe"] * c["num_experts"] * s["expert_mm"] * BYTES[c["param_dtype"]]
    return non_expert_weight_bytes(c) + experts


def kv_bytes_per_token(c: dict) -> int:
    """The latent row of one position, all MLA layers held. (The recurrent
    state is counted by slot in ``decode_step``.)"""
    s = _sizes(c)
    return s["n_mla"] * (s["R"] + s["dp"]) * BYTES[c["dtype"]]


def state_bytes_per_slot(c: dict) -> int:
    """One sequence's recurrent state (float32) and convolution tails, all
    KDA layers held."""
    s = _sizes(c)
    tails = (c["linear_attn_config"]["short_conv_kernel_size"] - 1) * 3 * s["H"] * s["d"]
    return s["n_kda"] * (s["H"] * s["d"] * s["d"] * 4 + tails * BYTES[c["dtype"]])


def experts_touched(c: dict, batch: float) -> float:
    """Held experts of one layer that at least one of ``batch`` tokens picks,
    expected under uniform routing over all routed experts."""
    share = c["num_experts_per_token"] / c["published"]["num_experts"]
    return c["num_experts"] * (1.0 - (1.0 - share) ** batch)


def _token_matmul_ops(c: dict) -> float:
    """Multiply-adds x 2 of one token through every held layer's matrices:
    the picks that land here are ``per_token x held / routed`` on average."""
    s = _sizes(c)
    here = c["num_experts_per_token"] * c["num_experts"] / c["published"]["num_experts"]
    return 2 * (
        s["n_kda"] * s["kda_mm"] + s["n_mla"] * s["mla_mm"] + s["n_dense"] * s["dense_mm"]
        + s["n_moe"] * (s["router"] + s["shared_mm"] + here * s["expert_mm"])
    )


def _kda_token_ops(c: dict) -> float:
    """The recurrence of one token, all KDA layers and heads: decay the state
    (1 a cell), S'^T k, the rank-one write, S^T q (2 a cell each)."""
    s = _sizes(c)
    return s["n_kda"] * s["H"] * 7 * s["d"] * s["d"]


def decode_step(c: dict, batch: float, context_tokens: float, touched: float | None = None):
    """(operations, bytes) of one decode step over ``batch`` sequences whose
    contexts hold ``context_tokens`` positions together. Bytes: every
    non-expert weight and the head once; each held expert that at least one
    token picks (``touched``: their count over all expert layers as the
    program's counter gave it, or, where no counter was read, expected under
    uniform routing); each live sequence's state read and written once; the
    live latent rows read and one written a sequence."""
    s = _sizes(c)
    if touched is None:
        touched = s["n_moe"] * experts_touched(c, batch)
    matmul = batch * (_token_matmul_ops(c) + 2 * s["D"] * c["vocab_size"])
    # absorbed MLA: scores over [c; k_pe] and values over c, per live row and
    # head (absorbing kv_b costs what expanding one token's latent costs)
    attn = 2 * s["n_mla"] * s["Hm"] * (2 * s["R"] + s["dp"]) * context_tokens
    ops = matmul + attn + batch * _kda_token_ops(c)
    nbytes = (
        non_expert_weight_bytes(c)
        + touched * s["expert_mm"] * BYTES[c["param_dtype"]]
        + 2 * batch * state_bytes_per_slot(c)
        + kv_bytes_per_token(c) * (context_tokens + batch)
    )
    return ops, nbytes


def prefill(c: dict, tokens: int):
    """(operations, bytes) of prefilling one fresh prompt of ``tokens``: the
    head runs on the last position only; MLA expands keys and values per head
    and attends causally; the state is written once."""
    s = _sizes(c)
    matmul = tokens * _token_matmul_ops(c) + 2 * s["D"] * c["vocab_size"]
    attn = 2 * s["n_mla"] * s["Hm"] * (s["dn"] + s["dp"] + s["dv"]) * tokens * (tokens + 1) / 2
    ops = matmul + attn + tokens * _kda_token_ops(c)
    nbytes = weight_bytes(c) + kv_bytes_per_token(c) * tokens + state_bytes_per_slot(c)
    return ops, nbytes
