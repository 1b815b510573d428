"""The Nemotron-H family (Mamba-2 state-space blocks with a state per
sequence, one grouped-query attention block in eleven whose keys and values lie
in blocks, routed squared-ReLU experts computed in a latent of which this chip
holds its share) as the benchmark reaches it: served through the paged engine.
Configurations use the published key names; ``n_routed_experts`` is the count
of experts held here from ``expert_offset``, ``published.n_routed_experts`` the
router's width, ``hybrid_override_pattern`` the letters of the blocks held
(the first ``num_hidden_layers`` of ``published.hybrid_override_pattern``).
The plain reference is ``reference/nemotron_h_ref.py``.

Provides ``model_config``, ``check``, ``shrink``, ``init_params`` and what a
serving family owes the roofline readers: ``decode_step``, ``prefill``,
``weight_bytes``, ``kv_bytes_per_token``, ``state_bytes_per_slot``,
``experts_touched`` (see README.md, "A family").
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
REFERENCE_ALONE = ("fp8", "bf16", "ungrouped_norm", "unsquared")  # the reference computed so
CACHE_WRONGED = ("displaced", "swapped_tables", "stale_state")  # the program, its cache wronged
KV_ROWS = 32  # of each compared request, the newest: the decode steps' and the prompt's last


def model_config(c: dict, traffic: dict):
    import jax.numpy as jnp

    from ray_tpu.models.nemotron_h import NemotronHConfig

    # What the published file says that the program has one way of doing.
    assert not (c["attention_bias"] or c["mamba_proj_bias"] or c["mlp_bias"] or c["use_bias"])
    assert c["use_conv_bias"] and c["mamba_hidden_act"] == "silu" and not c["tie_word_embeddings"]
    assert c["n_group"] == c["topk_group"] == c["n_shared_experts"] == 1
    assert c["expand"] * c["hidden_size"] == c["mamba_num_heads"] * c["mamba_head_dim"]
    assert c["layer_norm_epsilon"] == c["norm_eps"] and c["sliding_window"] is None
    cfg = NemotronHConfig(
        vocab_size=c["vocab_size"],
        d_model=c["hidden_size"],
        pattern=c["published"]["hybrid_override_pattern"],
        n_layer=c["num_hidden_layers"],
        mamba_heads=c["mamba_num_heads"],
        mamba_head_dim=c["mamba_head_dim"],
        ssm_groups=c["n_groups"],
        ssm_state=c["ssm_state_size"],
        conv_kernel=c["conv_kernel"],
        time_step_min=c["time_step_min"],
        time_step_max=c["time_step_max"],
        time_step_floor=c["time_step_floor"],
        n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"],
        head_dim=c["head_dim"],
        moe_latent=c["moe_latent_size"],
        moe_d_ff=c["moe_intermediate_size"],
        shared_d_ff=c["moe_shared_expert_intermediate_size"],
        n_experts=c["published"]["n_routed_experts"],
        experts_held=c["n_routed_experts"],
        expert_offset=c["expert_offset"],
        experts_per_token=c["num_experts_per_tok"],
        routed_scaling=float(c["routed_scaling_factor"]),
        renormalize=c["norm_topk_prob"],
        hidden_act=c["mlp_hidden_act"],
        max_seq=traffic["engine"]["max_seq"],
        state_slots=traffic["engine"]["max_slots"],
        rms_eps=c["layer_norm_epsilon"],
        dtype=jnp.dtype(c["dtype"]),
        param_dtype=jnp.dtype(c["param_dtype"]),
    )
    assert cfg.held == c["hybrid_override_pattern"], (cfg.held, c["hybrid_override_pattern"])
    return cfg


def init_params(key, cfg):
    from ray_tpu.models import nemotron_h

    return nemotron_h.init_params(key, cfg)


def shrink(c: dict) -> dict:
    """The tiny keys of a CPU rehearsal: five blocks with every kind among
    them, four of eight experts held."""
    return {
        **c, "hidden_size": 64, "expand": 1, "mamba_num_heads": 4, "mamba_head_dim": 16,
        "n_groups": 2, "ssm_state_size": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "moe_latent_size": 16, "moe_intermediate_size": 32, "intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 48, "n_routed_experts": 4, "num_experts_per_tok": 3,
        "num_hidden_layers": 5, "hybrid_override_pattern": "ME*ME", "vocab_size": 512,
        "published": {**c["published"], "n_routed_experts": 8, "hybrid_override_pattern": "ME*MEME"},
    }


def check(c: dict, traffic: dict, seed: int, who: str, devices=None) -> dict:
    """``program`` is what the cell times: an ``LLMEngine`` built as the
    replica builds it (the mix's settings, the weights its initialiser draws
    from the seed, selection bias balanced), driven by ``add_request`` and
    ``step``. Three requests run first (``CHURN``); then one prompt of a length
    from the mix's own table and one of 77 tokens are admitted into the slots
    and blocks the churn left, and prefilled and decoded three steps beside the
    request that stayed. Four numbers against the reference's full forward
    over the same weights:

    - ``logits_rel_err``: the logits the engine samples from (the next token
      is forced on it where it would sample);
    - ``state_rel_err``: each of the two slots' recurrent state ``[M blocks, H,
      P, N]`` and convolution tail as they lie in the pool afterwards, against
      the reference's token-by-token state after as many tokens (the larger of
      the two parts' errors: their scales differ);
    - ``kv_rel_err``: the newest ``KV_ROWS`` rows of keys and values of each
      of the two sequences, gathered through the block table the request was
      given: where they were written;
    - ``route_agree_pct``: the share of the long prompt's (token, E block,
      pick) choices on which program and reference agree, so that an error
      raised by flipped near-tie picks is seen for what it is.

    ``fp8`` and ``bf16`` (every matmul operand rounded so), ``ungrouped_norm``
    (the gated norm over all channels at once) and ``unsquared`` (the experts'
    activation a plain ReLU) put the reference computed that way in the
    program's place, over the weights the engine would draw. The other
    controls are the program with its cache wronged after the first decode
    step: ``displaced`` (block tables shifted by one entry), ``swapped_tables``
    (the two requests' block tables exchanged), ``stale_state`` (their
    recurrent states and tails exchanged)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import model_build
    from benchmarks.reference import nemotron_h_ref
    from benchmarks.reference.common import rel_err
    from ray_tpu.llm import LLMEngine, SamplingParams
    from ray_tpu.models import nemotron_h

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

    def compared(logits):  # the last prompt position and the K after it
        return jnp.concatenate([logits[i, n - 1 : n + K] for i, n in enumerate(lens)])

    def newest_of(kv):  # [* blocks, sequences, positions, 2 KH Dh] -> the compared rows
        return jnp.concatenate([kv[:, i, rows] for i, rows in enumerate(newest)], axis=1)

    def state_err(state, conv, inner):
        return max(rel_err(state, inner["state"]), rel_err(conv, inner["conv"]))

    ref = jax.jit(functools.partial(nemotron_h_ref.forward, c=c, inner=True, keep_at=ended))
    llm_config = model_build.llm_config(c, traffic, seed)
    if who in REFERENCE_ALONE:
        weights = nemotron_h.init_params(jax.random.key(llm_config.seed), llm_config.model_config)
        how = {"quant": who} if who in ("fp8", "bf16") else {"wrong": who}
        ctl = jax.jit(functools.partial(nemotron_h_ref.forward, c=c, inner=True, keep_at=ended, **how))
        got, got_inner = ctl(weights, jnp.asarray(tokens))
        want, inner = ref(weights, jnp.asarray(tokens))
        return {
            "logits_rel_err": rel_err(compared(got), compared(want)),
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
        swap = lambda x: x.at[:, a].set(x[:, b]).at[:, b].set(x[:, a])  # noqa: E731
        engine.pool = {**engine.pool, "state": swap(engine.pool["state"]), "conv": swap(engine.pool["conv"])}
    while not all(engine.requests[r].finished for r in seen):
        engine.step()
    got = jnp.stack([x for rows in seen.values() for x in rows])
    out = {"logits_rel_err": rel_err(got, compared(want))}
    slots = jnp.asarray([a, b])
    out["state_rel_err"] = state_err(
        engine.pool["state"][:, slots], engine.pool["conv"][:, slots].astype(jnp.float32), inner
    )
    # [* blocks, blocks, KH, block, Dh]; only the two tables' blocks leave the device
    lie = []
    for i, rows in enumerate(newest):
        k, v = (
            np.asarray(engine.pool[part][:, given[i]].astype(jnp.float32)).transpose(0, 1, 3, 2, 4)
            for part in ("k", "v")
        )  # [* blocks, W, block, KH, Dh]
        flat = lambda x: x.reshape(x.shape[0], -1, x.shape[3] * x.shape[4])[:, rows]  # noqa: E731
        lie.append(np.concatenate([flat(k), flat(v)], axis=-1))
    out["kv_rel_err"] = rel_err(jnp.concatenate(lie, axis=1), newest_of(inner["kv"]))
    if who == "program":  # the long prompt's prefill once more, for its picks
        cfg, bs = llm_config.model_config, llm_config.kv_block_size
        n = lens[0]
        bucket = min(x for x in llm_config.prefill_buckets if x >= n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = tokens[0, :n]
        blocks = -(-bucket // bs)
        *_, picks = jax.jit(functools.partial(
            nemotron_h.paged_prefill, cfg=cfg, block_size=bs, with_picks=True,
        ))(
            engine.params, jnp.asarray(toks), jnp.asarray(n, jnp.int32), jnp.asarray(0, jnp.int32),
            jnp.arange(1, blocks + 1, dtype=jnp.int32), nemotron_h.init_pool(cfg, blocks + 1, bs, 0),
        )
        same = np.sort(np.asarray(picks[:, :n]), -1) == np.sort(np.asarray(inner["picks"][:, 0, :n]), -1)
        out["route_agree_pct"] = 100.0 * float(same.mean())
    return out


# -- operations and bytes that the algorithm needs (flops_bytes.py says what "needs" means)


def _sizes(c: dict) -> dict:
    D, H, P, G, N = (c["hidden_size"], c["mamba_num_heads"], c["mamba_head_dim"],
                     c["n_groups"], c["ssm_state_size"])
    Hq, KH, Dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    Dl, Fm, Fs = c["moe_latent_size"], c["moe_intermediate_size"], c["moe_shared_expert_intermediate_size"]
    held = c["hybrid_override_pattern"]
    conv_dim = H * P + 2 * G * N
    return {
        "D": D, "H": H, "P": P, "N": N, "Hq": Hq, "KH": KH, "Dh": Dh, "conv_dim": conv_dim,
        "n_m": held.count("M"), "n_a": held.count("*"), "n_e": held.count("E"),
        # weights that take part in a matrix multiplication, per block of a kind
        "m_mm": D * (H * P + conv_dim + H) + H * P * D,
        "m_other": (c["conv_kernel"] + 1) * conv_dim + H * P,  # convolution and bias, the gated norm
        "m_f32": 3 * H,  # dt_bias, A_log and D, float32 like the router
        "a_mm": D * Hq * Dh + 2 * D * KH * Dh + Hq * Dh * D,
        "e_mm": 2 * D * Dl + 2 * D * Fs,  # the latent pair and the shared expert
        "expert_mm": 2 * Dl * Fm,
        "router": D * c["published"]["n_routed_experts"],  # float32, with its bias
    }


def non_expert_weight_bytes(c: dict) -> int:
    """Every weight a step reads whatever the routing: the Mamba and the
    attention blocks, routers (float32), latent pairs and shared experts, each
    block's norm, the final norm and the head over the vocabulary held. The
    embedding table is a gather of a few rows and is left out."""
    s, b = _sizes(c), BYTES[c["param_dtype"]]
    n = (
        s["n_m"] * (s["m_mm"] + s["m_other"]) + s["n_a"] * s["a_mm"] + s["n_e"] * s["e_mm"]
        + s["D"] * c["num_hidden_layers"] + s["D"] + s["D"] * c["vocab_size"]
    )
    f32 = s["n_m"] * s["m_f32"] + s["n_e"] * (s["router"] + c["published"]["n_routed_experts"])
    return n * b + f32 * 4


def weight_bytes(c: dict) -> int:
    """All weights held here but the embedding table: what a prefill reads
    whose tokens reach every expert held."""
    s = _sizes(c)
    experts = s["n_e"] * c["n_routed_experts"] * s["expert_mm"] * BYTES[c["param_dtype"]]
    return non_expert_weight_bytes(c) + experts


def kv_bytes_per_token(c: dict) -> int:
    """The key and the value of one position, all attention blocks held. (The
    recurrent state is counted by slot in ``decode_step``.)"""
    s = _sizes(c)
    return s["n_a"] * 2 * s["KH"] * s["Dh"] * BYTES[c["dtype"]]


def state_bytes_per_slot(c: dict) -> int:
    """One sequence's recurrent state (float32) and convolution tails, all
    Mamba blocks held."""
    s = _sizes(c)
    tails = (c["conv_kernel"] - 1) * s["conv_dim"]
    return s["n_m"] * (s["H"] * s["P"] * s["N"] * 4 + tails * BYTES[c["dtype"]])


def experts_touched(c: dict, batch: float) -> float:
    """Held experts of one block that at least one of ``batch`` tokens picks,
    expected under uniform routing over all routed experts."""
    share = c["num_experts_per_tok"] / c["published"]["n_routed_experts"]
    return c["n_routed_experts"] * (1.0 - (1.0 - share) ** batch)


def _token_matmul_ops(c: dict) -> float:
    """Multiply-adds x 2 of one token through every held block's matrices:
    the picks that land here are ``per_token x held / routed`` on average."""
    s = _sizes(c)
    here = c["num_experts_per_tok"] * c["n_routed_experts"] / c["published"]["n_routed_experts"]
    return 2 * (
        s["n_m"] * s["m_mm"] + s["n_a"] * s["a_mm"]
        + s["n_e"] * (s["router"] + s["e_mm"] + here * s["expert_mm"])
    )


def _ssm_token_ops(c: dict) -> float:
    """The recurrence of one token, all Mamba blocks and heads: decay the
    state (1 a cell), the rank-one write and ``h C`` (2 a cell each)."""
    s = _sizes(c)
    return s["n_m"] * s["H"] * 5 * s["P"] * s["N"]


def decode_step(c: dict, batch: float, context_tokens: float, touched: float | None = None):
    """(operations, bytes) of one decode step over ``batch`` sequences whose
    contexts hold ``context_tokens`` positions together. Bytes: every
    non-expert weight and the head once; each held expert that at least one
    token picks (``touched``: their count over all E blocks as the program's
    counter gave it, or, where no counter was read, expected under uniform
    routing); each live sequence's state and tails read and written once; the
    live keys and values read and one pair written a sequence."""
    s = _sizes(c)
    if touched is None:
        touched = s["n_e"] * experts_touched(c, batch)
    matmul = batch * (_token_matmul_ops(c) + 2 * s["D"] * c["vocab_size"])
    attn = 2 * s["n_a"] * s["Hq"] * 2 * s["Dh"] * context_tokens  # scores and values, per live row and head
    ops = matmul + attn + batch * _ssm_token_ops(c)
    nbytes = (
        non_expert_weight_bytes(c)
        + touched * s["expert_mm"] * BYTES[c["param_dtype"]]
        + 2 * batch * state_bytes_per_slot(c)
        + kv_bytes_per_token(c) * (context_tokens + batch)
    )
    return ops, nbytes


def prefill(c: dict, tokens: int, touched: float | None = None):
    """(operations, bytes) of prefilling one fresh prompt of ``tokens``: the
    head runs on the last position only; attention is causal; the state is
    written once. ``touched``: the held experts the prompt's tokens reached,
    over all E blocks, as the program counted them (None: every one held)."""
    s = _sizes(c)
    matmul = tokens * _token_matmul_ops(c) + 2 * s["D"] * c["vocab_size"]
    attn = 2 * s["n_a"] * s["Hq"] * 2 * s["Dh"] * tokens * (tokens + 1) / 2
    ops = matmul + attn + tokens * _ssm_token_ops(c)
    if touched is None:
        touched = s["n_e"] * c["n_routed_experts"]
    nbytes = (
        non_expert_weight_bytes(c) + touched * s["expert_mm"] * BYTES[c["param_dtype"]]
        + kv_bytes_per_token(c) * tokens + state_bytes_per_slot(c)
    )
    return ops, nbytes
