"""The ``mla_moe`` family (latent attention with a rotated shared key and a
low-rank query in every layer, group-limited routing over experts of which
this chip holds its share) as the benchmark reaches it: served through the
paged engine. Configurations use the published key names; ``n_routed_experts``
is the count of experts held here from ``expert_offset``,
``published.n_routed_experts`` the router's width. The plain reference is
``reference/mla_moe_ref.py``.

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
# the first and the last leave their slots and blocks to the two; the second
# stays and shares their steps.
CHURN = ((100, 2), (120, DECODE_STEPS + 8), (90, 3))
REFERENCE_ALONE = ("fp8", "bf16", "norope", "noyarn")  # the reference, computed wrongly on purpose
CACHE_WRONGED = ("displaced", "swapped_tables")  # the engine, its tables wronged after the first decode step
LATENT_ROWS = 32  # of each compared request, the newest: the decode steps' and the prompt's last


def model_config(c: dict, traffic: dict):
    import jax.numpy as jnp

    from ray_tpu.models.mla_moe import MlaMoeConfig

    # What the published file says that the program has one way of doing.
    assert c["scoring_func"] == "sigmoid" and c["hidden_act"] == "silu" and c["moe_layer_freq"] == 1
    assert c["topk_method"] == "none" and not c["attention_bias"] and not c["tie_word_embeddings"]
    rs = c["rope_scaling"]
    assert rs["type"] == "yarn"
    return MlaMoeConfig(
        vocab_size=c["vocab_size"],
        n_layer=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        n_head=c["num_attention_heads"],
        q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]),
        rope_factor=float(rs["factor"]),
        rope_original_max=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        d_ff=c["intermediate_size"],
        first_k_dense=c["first_k_dense_replace"],
        moe_d_ff=c["moe_intermediate_size"],
        n_experts=c["published"]["n_routed_experts"],
        experts_held=c["n_routed_experts"],
        expert_offset=c["expert_offset"],
        experts_per_token=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"],
        n_group=c["n_group"],
        topk_group=c["topk_group"],
        routed_scaling=c["routed_scaling_factor"],
        renormalize=c["norm_topk_prob"],
        max_seq=traffic["engine"]["max_seq"],
        rms_eps=c["rms_norm_eps"],
        dtype=jnp.dtype(c["dtype"]),
        param_dtype=jnp.dtype(c["param_dtype"]),
    )


def init_params(key, cfg):
    from ray_tpu.models import mla_moe

    return mla_moe.init_params(key, cfg)


def shrink(c: dict) -> dict:
    """The tiny keys of a CPU rehearsal: a dense layer and two expert layers;
    sixteen experts in two groups of which a token is held to one, the first
    four held here (half of group 0, as at the real size); YaRN stretched from
    an original context of 32."""
    return {
        **c, "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 2,
        "num_key_value_heads": 2, "q_lora_rank": 24, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "moe_intermediate_size": 32, "n_routed_experts": 4, "num_experts_per_tok": 2,
        "n_group": 2, "topk_group": 1, "num_hidden_layers": 3, "vocab_size": 512,
        "rope_scaling": {**c["rope_scaling"], "factor": 8, "original_max_position_embeddings": 32},
        "published": {**c["published"], "n_routed_experts": 16},
    }


def check(c: dict, traffic: dict, seed: int, who: str, devices=None) -> dict:
    """``program`` is what the cell times: an ``LLMEngine`` built as the
    replica builds it (the mix's settings, the weights its initialiser draws
    from the seed, routers centred), driven by ``add_request`` and ``step``.
    Three requests run first (``CHURN``); then one prompt of a length from the
    mix's own table and one of 77 tokens are admitted into the slots and
    blocks the churn left, and prefilled and decoded three steps beside the
    request that stayed. ``logits_rel_err``: the logits the engine samples
    from, against the reference's full forward over the same weights; the next
    token is forced on the engine where it would sample. ``latent_rel_err``:
    the newest ``LATENT_ROWS`` rows of each of the two sequences as they lie in
    the latent pool afterwards, gathered through the block table the request
    was given, against the reference's ``[c^; R_t k_r]`` at those positions:
    it tells where rows were written and by which rotation. Also
    ``route_agree_pct``: the share of the long prompt's (token, expert layer,
    pick) choices on which program and reference agree, so that an error
    raised by flipped near-tie picks is seen for what it is.

    ``fp8``, ``bf16``, ``norope`` (rotation left out) and ``noyarn`` (plain
    frequencies, plain ``(d_n + d_r)^-1/2``) put the reference computed that
    way in the program's place, over the weights the engine would draw. The
    other controls are the program with its cache wronged after the first
    decode step: ``displaced`` (block tables shifted by one entry),
    ``swapped_tables`` (the two requests' block tables exchanged)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import model_build
    from benchmarks.reference import mla_moe_ref
    from benchmarks.reference.common import rel_err
    from ray_tpu.llm import LLMEngine, SamplingParams
    from ray_tpu.models import mla_moe

    if who not in ("program", *REFERENCE_ALONE, *CACHE_WRONGED):
        raise SystemExit(f"unknown --who {who!r}")
    K = DECODE_STEPS
    rng = np.random.default_rng(seed)
    longest = max(traffic["engine"]["prefill_buckets"]) - K - 1
    lens = [min(int(rng.choice(traffic["prompt_tokens"])), longest), min(SHORT_PROMPT, longest)]
    # as wide as the mix's longest prompt whichever was drawn: one shape of the reference for every seed
    width = min(max(traffic["prompt_tokens"] + [SHORT_PROMPT]), longest) + K
    tokens = rng.integers(0, c["vocab_size"], size=(len(lens), width)).astype(np.int32)
    newest = [slice(max(n + K - LATENT_ROWS, 0), n + K) for n in lens]

    def compared(logits):  # the last prompt position and the K after it
        return jnp.concatenate([logits[i, n - 1 : n + K] for i, n in enumerate(lens)])

    def newest_of(latents):  # [layers, sequences, positions, 576] -> the compared rows
        return jnp.concatenate([latents[:, i, rows] for i, rows in enumerate(newest)], axis=1)

    ref = jax.jit(functools.partial(mla_moe_ref.forward, c=c, quant=None, inner=True))
    llm_config = model_build.llm_config(c, traffic, seed)
    if who in REFERENCE_ALONE:
        weights = mla_moe.init_params(jax.random.key(llm_config.seed), llm_config.model_config)
        how = {"quant": who} if who in ("fp8", "bf16") else {"variant": who}
        ctl = jax.jit(functools.partial(mla_moe_ref.forward, c=c, inner=True, **how))
        got, got_inner = ctl(weights, jnp.asarray(tokens))
        want, inner = ref(weights, jnp.asarray(tokens))
        return {
            "logits_rel_err": rel_err(compared(got), compared(want)),
            "latent_rel_err": rel_err(newest_of(got_inner["latents"]), newest_of(inner["latents"])),
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
    assert min(a, b) >= 0
    given = engine.block_tables[[a, b]].copy()  # the two requests' tables, before any is wronged
    if who == "displaced":
        engine.block_tables[:] = np.roll(engine.block_tables, 1, axis=1)
    elif who == "swapped_tables":
        engine.block_tables[[a, b]] = engine.block_tables[[b, a]]
    while not all(engine.requests[r].finished for r in seen):
        engine.step()
    got = jnp.stack([x for rows in seen.values() for x in rows])
    out = {"logits_rel_err": rel_err(got, compared(want))}
    # [layers, blocks, block, 576 and zeros to whole tiles]; only the two tables' blocks leave the device
    ckv, width = engine.pool["ckv"], inner["latents"].shape[-1]
    lie = [
        ckv[:, given[i]].reshape(ckv.shape[0], -1, ckv.shape[-1])[:, rows, :width].astype(jnp.float32)
        for i, rows in enumerate(newest)
    ]
    out["latent_rel_err"] = rel_err(jnp.concatenate(lie, axis=1), newest_of(inner["latents"]))
    if who == "program":  # the long prompt's prefill once more, for its picks
        cfg, bs = llm_config.model_config, llm_config.kv_block_size
        n = lens[0]
        bucket = min(x for x in llm_config.prefill_buckets if x >= n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = tokens[0, :n]
        blocks = -(-bucket // bs)
        *_, picks = jax.jit(functools.partial(
            mla_moe.paged_prefill, cfg=cfg, block_size=bs, with_picks=True,
        ))(
            engine.params, jnp.asarray(toks), jnp.asarray(n, jnp.int32), jnp.asarray(0, jnp.int32),
            jnp.arange(1, blocks + 1, dtype=jnp.int32), mla_moe.init_pool(cfg, blocks + 1, bs),
        )
        same = np.sort(np.asarray(picks[:, :n]), -1) == np.sort(np.asarray(inner["picks"][:, 0, :n]), -1)
        out["route_agree_pct"] = 100.0 * float(same.mean())
    return out


# -- operations and bytes that the algorithm needs (flops_bytes.py says what "needs" means)


def _sizes(c: dict) -> dict:
    D, H = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv, R = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"]
    rq = c["q_lora_rank"]
    query = D * rq + rq * H * (dn + dr)
    dense = c["first_k_dense_replace"]
    return {
        "D": D, "H": H, "dn": dn, "dr": dr, "dv": dv, "R": R,
        "n_layers": c["num_hidden_layers"], "n_dense": dense,
        "n_moe": c["num_hidden_layers"] - dense,
        # weights that take part in a matrix multiplication, per layer of a kind
        "mla_mm": query + D * (R + dr) + R * H * (dn + dv) + H * dv * D,
        "mla_other": R + rq,  # kv_norm, q_norm
        "dense_mm": 3 * D * c["intermediate_size"],
        "expert_mm": 3 * D * c["moe_intermediate_size"],
        "shared_mm": 3 * D * c["moe_intermediate_size"] * c["n_shared_experts"],
        "router": D * c["published"]["n_routed_experts"],  # float32, no bias
    }


def non_expert_weight_bytes(c: dict) -> int:
    """Every weight a step reads whatever the routing: the latent attention
    of every layer, the dense layer's MLP, routers (float32) and shared
    experts, both norms of each layer, the final norm and the head over the
    vocabulary held. The embedding table is a gather of a few rows and is left
    out."""
    s, b = _sizes(c), BYTES[c["param_dtype"]]
    n = (
        s["n_layers"] * (s["mla_mm"] + s["mla_other"] + 2 * s["D"])
        + s["n_dense"] * s["dense_mm"] + s["n_moe"] * s["shared_mm"]
        + s["D"] + s["D"] * c["vocab_size"]
    )
    return n * b + s["n_moe"] * s["router"] * 4


def weight_bytes(c: dict) -> int:
    """All weights held here but the embedding table: what a prefill reads,
    whose tokens reach every expert held."""
    s = _sizes(c)
    experts = s["n_moe"] * c["n_routed_experts"] * s["expert_mm"] * BYTES[c["param_dtype"]]
    return non_expert_weight_bytes(c) + experts


def kv_bytes_per_token(c: dict) -> int:
    """The latent row of one position, every layer."""
    s = _sizes(c)
    return s["n_layers"] * (s["R"] + s["dr"]) * BYTES[c["dtype"]]


def experts_touched(c: dict, batch: float) -> float:
    """Held experts of one layer that at least one of ``batch`` tokens picks,
    expected if every expert of the model were as likely as any other."""
    share = c["num_experts_per_tok"] / c["published"]["n_routed_experts"]
    return c["n_routed_experts"] * (1.0 - (1.0 - share) ** batch)


def _token_matmul_ops(c: dict) -> float:
    """Multiply-adds x 2 of one token through every held layer's matrices:
    the picks that land here are ``per_token x held / routed`` on average."""
    s = _sizes(c)
    here = c["num_experts_per_tok"] * c["n_routed_experts"] / c["published"]["n_routed_experts"]
    return 2 * (
        s["n_layers"] * s["mla_mm"] + s["n_dense"] * s["dense_mm"]
        + s["n_moe"] * (s["router"] + s["shared_mm"] + here * s["expert_mm"])
    )


def decode_step(c: dict, batch: float, context_tokens: float, touched: float | None = None):
    """(operations, bytes) of one decode step over ``batch`` sequences whose
    contexts hold ``context_tokens`` positions together. Bytes: every
    non-expert weight and the head once; each held expert that at least one
    token picks (``touched``: their count over all expert layers as the
    program's counter gave it, or, where no counter was read, expected under
    even routing); the live latent rows read and one written a sequence.
    Operations: the matrices, and the absorbed attention, ``2 H (2 r_kv + d_r)``
    a live row and layer: scores over ``[c; k_r]``, values over ``c``
    (absorbing ``W_ukv`` costs what expanding one token's latent costs, and is
    among the matrices)."""
    s = _sizes(c)
    if touched is None:
        touched = s["n_moe"] * experts_touched(c, batch)
    matmul = batch * (_token_matmul_ops(c) + 2 * s["D"] * c["vocab_size"])
    attn = 2 * s["n_layers"] * s["H"] * (2 * s["R"] + s["dr"]) * context_tokens
    nbytes = (
        non_expert_weight_bytes(c)
        + touched * s["expert_mm"] * BYTES[c["param_dtype"]]
        + kv_bytes_per_token(c) * (context_tokens + batch)
    )
    return matmul + attn, nbytes


def prefill(c: dict, tokens: int):
    """(operations, bytes) of prefilling one fresh prompt of ``tokens``: the
    head runs on the last position only; keys and values are expanded per head
    and attended causally; the rows are written once."""
    s = _sizes(c)
    matmul = tokens * _token_matmul_ops(c) + 2 * s["D"] * c["vocab_size"]
    attn = 2 * s["n_layers"] * s["H"] * (s["dn"] + s["dr"] + s["dv"]) * tokens * (tokens + 1) / 2
    return matmul + attn, weight_bytes(c) + kv_bytes_per_token(c) * tokens
