"""The Solar Open 2 family (three delta-rule layers with a recurrent state per
sequence to one gated grouped-query attention layer without rotation, routed
experts in every layer of which this chip holds its share) as the benchmark
reaches it: served through the paged engine, whose cache for it is keys and
values per head in blocks, a state per slot and a convolution tail per slot.
Configurations use the published key names; ``n_routed_experts`` is the count
of experts held here from ``expert_offset``, ``published.n_routed_experts`` the
router's width; layers 0..``num_hidden_layers`` - 1 are held and ``gqa_layers``
names those of them that are attention. The plain reference is
``reference/solar_open2_ref.py``.

Provides ``model_config``, ``check``, ``shrink``, ``init_params`` and what a
serving family owes the roofline readers: ``decode_step``, ``prefill``,
``weight_bytes``, ``kv_bytes_per_token``, ``state_bytes_per_slot``,
``experts_touched`` (see README.md, "A family"). A chunk's attention is counted
over the keys it may see from its ``start``.
"""

from __future__ import annotations

import functools

from benchmarks.flops_bytes import BYTES

DECODE_STEPS = 3
SHORT_TAIL = 9  # the second prompt: one chunk and so many tokens, its second chunk
SHORT_PROMPT = 77  # the second prompt where the mix prefills no prompt in chunks
# (tokens, answer's length) of the requests that run before the compared two:
# the first and the last leave their slots, with a state in them, and their
# blocks to the two; the second stays and shares their steps.
CHURN = ((100, 2), (120, DECODE_STEPS + 8), (90, 3))
# the reference computed so, in the program's place
REFERENCE_ALONE = ("fp8", "bf16", "beta_unit", "ungated", "rotated", "stale_state", "lost_tail")
CACHE_WRONGED = ("displaced",)  # the program, a block of its tables wronged after the first decode step
KV_ROWS = 32  # of each compared request, the newest: the decode steps' and the prompt's last


def _kinds(c: dict) -> list:
    gqa = set(c["gqa_layers"])
    return ["gqa" if i in gqa else "kda" for i in range(c["num_hidden_layers"])]


def model_config(c: dict, traffic: dict):
    import jax.numpy as jnp

    from ray_tpu.models.solar_open2 import SolarOpen2Config

    # What the published file says that the program has one way of doing.
    assert not c["use_rope"] and c["use_gqa_gate"] and not c["kda_use_full_proj"]
    assert c["first_k_dense_replace"] == 0 and not c["tie_word_embeddings"]
    la = c["linear_attn_config"]
    assert la["num_kv_heads"] in (None, la["num_heads"])
    return SolarOpen2Config(
        vocab_size=c["vocab_size"],
        d_model=c["hidden_size"],
        layer_kinds=tuple(_kinds(c)),
        kda_heads=la["num_heads"],
        kda_head_dim=la["head_dim"],
        conv_kernel=la["short_conv_kernel_size"],
        kda_gate_rank=c["assumed"]["kda_gate_rank"],
        kda_neg_eigval=c["kda_allow_neg_eigval"],
        n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"],
        head_dim=c["head_dim"],
        moe_d_ff=c["moe_intermediate_size"],
        n_experts=c["published"]["n_routed_experts"],
        experts_held=c["n_routed_experts"],
        expert_offset=c["expert_offset"],
        experts_per_token=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"],
        routed_scaling=float(c["routed_scaling_factor"]),
        renormalize=c["norm_topk_prob"],
        max_seq=traffic["engine"]["max_seq"],
        state_slots=traffic["engine"]["max_slots"],
        rms_eps=c["rms_norm_eps"],
        silent_ids=tuple(c.get("silent_ids", ())),
        dtype=jnp.dtype(c["dtype"]),
        param_dtype=jnp.dtype(c["param_dtype"]),
    )


def init_params(key, cfg):
    from ray_tpu.models import solar_open2

    return solar_open2.init_params(key, cfg)


def shrink(c: dict) -> dict:
    """The tiny keys of a CPU rehearsal: one whole period, four of eight
    experts held."""
    return {
        **c, "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32,
        "linear_attn_config": {**c["linear_attn_config"], "head_dim": 16, "num_heads": 2},
        "n_routed_experts": 4, "num_experts_per_tok": 2, "num_hidden_layers": 4, "gqa_layers": [0],
        "vocab_size": 512,
        "published": {**c["published"], "n_routed_experts": 8},
        "assumed": {**c["assumed"], "kda_gate_rank": 8},
    }


def check(c: dict, traffic: dict, seed: int, who: str, devices=None) -> dict:
    """``program`` is what the cell times: an ``LLMEngine`` built as the
    replica builds it (the mix's settings, chunked prefill among them, the
    weights its initialiser draws from the seed, selection bias balanced),
    driven by ``add_request`` and ``step``. Three requests run first
    (``CHURN``) and leave a state in their slots and rows in their blocks; then
    one prompt of a length from the mix's own table goes through its chunks
    beside the request that stayed, and one of a chunk and ``SHORT_TAIL``
    tokens joins it, whose second chunk begins from the state and the tail its
    first left; both decode three steps. Against the reference's
    full forward over the same weights, a sequence at a time:

    - ``logits_rel_err``: the logits the engine samples from (the next token
      is forced on it where it would sample);
    - ``kv_rel_err``: the newest ``KV_ROWS`` rows of keys and values of each
      of the two sequences in the GQA layers, gathered through the block
      tables the requests were given: where they were written;
    - ``state_rel_err``: each of the two slots' recurrent state ``[KDA layers,
      H, d, d]`` and convolution tail as they lie in the pool after the last
      chunk and after the last decode step, against the reference's
      token-by-token state after as many tokens (the larger of the two parts'
      errors: their scales differ);
    - ``route_agree_pct`` (``program`` alone): the share of the short prompt's
      (token, layer, pick) choices on which program and reference agree.

    ``fp8`` and ``bf16`` (every matmul operand rounded so), ``beta_unit``
    (``beta = sigmoid``), ``ungated`` (no ``sigmoid(gate)`` in the GQA layer),
    ``rotated`` (the GQA layer given RoPE), ``stale_state`` and ``lost_tail``
    (the state, or the convolution's tail, zero where the second chunk begins)
    put the reference computed that way in the program's place, over the
    weights the engine would draw. ``displaced`` is the program with one entry
    of each compared slot's block table wronged after the first decode step:
    the block its next rows go to is the one before it."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import model_build
    from benchmarks.reference import solar_open2_ref
    from benchmarks.reference.common import rel_err
    from ray_tpu.llm import LLMEngine, SamplingParams
    from ray_tpu.models import solar_open2

    if who not in ("program", *REFERENCE_ALONE, *CACHE_WRONGED):
        raise SystemExit(f"unknown --who {who!r}")
    K = DECODE_STEPS
    e = traffic["engine"]
    rng = np.random.default_rng(seed)
    longest = max(e["prefill_buckets"]) - K - 1
    chunk = e.get("prefill_chunk_tokens") or 0
    lens = [min(int(rng.choice(traffic["prompt_tokens"])), longest)]
    chunked = 0 < chunk and chunk + SHORT_TAIL <= min(lens[0], longest)
    lens.append(chunk + SHORT_TAIL if chunked else min(SHORT_PROMPT, longest))
    cut = chunk if chunked else min(lens) // 2  # where the controls of a chunk's start wrong the reference
    tokens = [rng.integers(0, c["vocab_size"], size=n + K).astype(np.int32) for n in lens]
    newest = [slice(max(n + K - KV_ROWS, 0), n + K) for n in lens]

    def reference(weights, **how):
        """(the compared logits, the compared rows of keys and values, the
        states and the tails after the prompt and after the last step) of
        both sequences, one after the other."""
        logits, kv, state, conv = [], [], [], []
        for toks, n, new in zip(tokens, lens, newest):
            fwd = jax.jit(functools.partial(
                solar_open2_ref.forward, c=c, inner=True, logits_at=tuple(range(n - 1, n + K)),
                kv_rows=new, state_at=(n, n + K), **how,
            ))
            got, inner = fwd(weights, jnp.asarray(toks))
            logits.append(got)
            kv.append(inner["kv"])
            state.append(inner["state"])
            conv.append(inner["conv"])
        cat = lambda xs, axis=0: jnp.concatenate(xs, axis=axis)  # noqa: E731
        return cat(logits), cat(kv, 1), cat(state), cat(conv), inner["picks"]

    def errors(got, want):
        return {
            "logits_rel_err": rel_err(got[0], want[0]),
            "kv_rel_err": rel_err(got[1], want[1]),
            "state_rel_err": max(rel_err(got[2], want[2]), rel_err(got[3], want[3])),
        }

    llm_config = model_build.llm_config(c, traffic, seed)
    if who in REFERENCE_ALONE:
        weights = solar_open2.init_params(jax.random.key(llm_config.seed), llm_config.model_config)
        how = {"quant": who} if who in ("fp8", "bf16") else {"wrong": who}
        if who in ("stale_state", "lost_tail"):
            how["cut_at"] = cut
        return errors(reference(weights, **how), reference(weights))

    engine = LLMEngine(llm_config)
    bs = llm_config.kv_block_size
    seen: dict = {f"r{i}": [] for i in range(len(lens))}
    after: dict = {}  # request -> [(state, tail) of its slot after the prompt, after the last step]
    slots, given, wronged = {}, {}, set()  # request -> its slot, the table it was given; those wronged

    def slot_state(slot):
        pool = engine.pool
        return np.asarray(pool["state"][:, slot]), np.asarray(pool["conv"][:, slot].astype(jnp.float32))

    def forced(logits, req):  # where the engine would sample: note the logits, force the token
        rows = seen.get(req.request_id)
        if rows is None:
            return 1  # a churn request: any token that is not its stop token
        rows.append(np.array(logits))
        i, j = int(req.request_id[1:]), len(rows) - 1
        if j == 0:  # the prompt's last chunk has just run: its slot's state, before any step
            # (a prompt prefilled whole samples before it takes the slot it was prefilled into)
            after[req.request_id] = [slot_state(req.slot if req.slot >= 0 else engine.slot_free.index(True))]
        return int(tokens[i][lens[i] + j]) if j < K else 0

    engine._sample = forced
    never = -1  # no token stops a request: each runs its max_tokens
    turns = -(-lens[0] // chunk) if chunked else 0  # the long prompt's prefill, a chunk a turn
    for n, (length, answer) in enumerate(CHURN):
        engine.add_request(
            f"churn{n}", rng.integers(0, c["vocab_size"], size=min(length, longest)).tolist(),
            SamplingParams(max_tokens=answer + (turns if n == 1 else 0), stop_token=never),
        )
    while not (engine.requests["churn0"].finished and engine.requests["churn2"].finished):
        engine.step()

    def admit(i):
        engine.add_request(
            f"r{i}", tokens[i][: lens[i]].tolist(), SamplingParams(max_tokens=K + 1, stop_token=never)
        )
        return engine.requests[f"r{i}"]

    def step():
        """One turn; then each compared request's slot and table as the engine's
        books have them, and, for ``displaced``, its table wronged once its
        first decode step has run."""
        engine.step()
        for r in reqs:
            if r.slot < 0:
                continue
            slots[r.request_id], given[r.request_id] = r.slot, np.array(r.blocks)
            if who == "displaced" and len(seen[r.request_id]) >= 2 and r.request_id not in wronged:
                at = int(engine.positions[r.slot]) // bs
                engine.block_tables[r.slot, at] = engine.block_tables[r.slot, at - 1]
                wronged.add(r.request_id)

    reqs = [admit(0)]
    if turns > 2:  # the long prompt's chunks but the last two, beside the request that stayed
        while reqs[0].slot < 0 or lens[0] - reqs[0].pf_next > 2 * chunk:
            step()
    reqs.append(admit(1))  # each takes a chunk a turn, by turns; both end within a turn of each other
    while not all(r.finished for r in reqs):
        step()
    assert engine.stats["state_resets"] == len(CHURN) + len(lens), engine.stats
    # a slot's rows stay as its request's last step left them until the slot is taken again
    for r in reqs:
        after[r.request_id].append(slot_state(slots[r.request_id]))
    pool = engine.pool
    got_logits = jnp.stack([x for rows in seen.values() for x in rows[: K + 1]])
    rows = []
    for i, new in enumerate(newest):  # [GQA layers, blocks, KH, block, Dh]; few blocks leave the device
        first = new.start // bs
        blocks = given[f"r{i}"][first : -(-new.stop // bs)]
        k, v = (
            np.asarray(pool[x][:, blocks].astype(jnp.float32)).transpose(0, 1, 3, 2, 4) for x in ("k", "v")
        )  # [layers, blocks, block, KH, Dh]
        flat = lambda x: x.reshape(x.shape[0], -1, x.shape[3] * x.shape[4])  # noqa: E731
        kv = np.concatenate([flat(k), flat(v)], axis=-1)
        rows.append(kv[:, new.start - first * bs : new.stop - first * bs])
    got = (
        got_logits, np.concatenate(rows, axis=1),
        np.stack([s for r in reqs for s, _ in after[r.request_id]]),
        np.stack([t for r in reqs for _, t in after[r.request_id]]),
    )
    picks = None
    if who == "program":  # the short prompt's prefill once more, whole, for its picks
        cfg, n = llm_config.model_config, lens[1]
        width = -(-n // bs) * bs
        toks = np.zeros((1, width), np.int32)
        toks[0, :n] = tokens[1][:n]
        *_, picks = jax.jit(functools.partial(
            solar_open2.paged_prefill, cfg=cfg, block_size=bs, with_picks=True,
        ))(
            engine.params, jnp.asarray(toks), jnp.asarray(n, jnp.int32), jnp.asarray(0, jnp.int32),
            jnp.arange(1, width // bs + 1, dtype=jnp.int32), solar_open2.init_pool(cfg, width // bs + 1, bs, 0),
        )
        picks = np.asarray(picks[:, :n])
    # The reference beside the weights alone: the engine's pool and programs go first.
    params = engine.params
    engine.pool = pool = None
    del engine
    gc.collect()
    want = reference(params)
    out = errors(got, want[:4])
    if picks is not None:
        same = np.sort(picks, -1) == np.sort(np.asarray(want[4][:, : lens[1]]), -1)
        out["route_agree_pct"] = 100.0 * float(same.mean())
    return out


# -- operations and bytes that the algorithm needs (flops_bytes.py says what "needs" means)


def _sizes(c: dict) -> dict:
    la = c["linear_attn_config"]
    kinds = _kinds(c)
    D, H, d, K = c["hidden_size"], la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    Hq, KH, Dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    r = c["assumed"]["kda_gate_rank"]
    return {
        "D": D, "H": H, "d": d, "K": K, "Hq": Hq, "KH": KH, "Dh": Dh,
        "n_layers": len(kinds), "n_kda": kinds.count("kda"), "n_gqa": kinds.count("gqa"),
        # weights that take part in a matrix multiplication, per layer of a kind
        "kda_mm": 3 * D * H * d + H * d * D + 2 * (D * r + r * H * d) + D * H,
        "kda_other": K * 3 * H * d + d,  # convolutions, the output norm
        "kda_f32": H * d + H,  # dt_bias and A_log, float32 like the router
        "gqa_mm": 3 * D * Hq * Dh + 2 * D * KH * Dh,  # W_q, W_g, W_o; W_k, W_v
        "expert_mm": 3 * D * c["moe_intermediate_size"],
        "shared_mm": 3 * D * c["moe_intermediate_size"] * c["n_shared_experts"],
        "router": D * c["published"]["n_routed_experts"],  # float32, with its bias
        "kv_layer": 2 * KH * Dh * BYTES[c["dtype"]],  # a position's key and value, one GQA layer
    }


def non_expert_weight_bytes(c: dict) -> int:
    """Every weight a step reads whatever the routing: mixers, routers
    (float32, with their bias) and shared experts, both norms of each layer,
    the final norm and the head over the vocabulary held. The embedding table
    is a gather of a few rows and is left out."""
    s, b = _sizes(c), BYTES[c["param_dtype"]]
    n = (
        s["n_kda"] * (s["kda_mm"] + s["kda_other"]) + s["n_gqa"] * s["gqa_mm"]
        + s["n_layers"] * (s["shared_mm"] + 2 * s["D"]) + s["D"] + s["D"] * c["vocab_size"]
    )
    f32 = s["n_kda"] * s["kda_f32"] + s["n_layers"] * (s["router"] + c["published"]["n_routed_experts"])
    return n * b + f32 * 4


def weight_bytes(c: dict) -> int:
    """All weights held here but the embedding table: what a prefill reads
    whose tokens reach every expert held."""
    s = _sizes(c)
    return non_expert_weight_bytes(c) + s["n_layers"] * c["n_routed_experts"] * s["expert_mm"] * BYTES[c["param_dtype"]]


def kv_bytes_per_token(c: dict) -> int:
    """The key and the value of one position, all GQA layers held. (The
    recurrent state is counted by slot: ``state_bytes_per_slot``.)"""
    s = _sizes(c)
    return s["n_gqa"] * s["kv_layer"]


def state_bytes_per_slot(c: dict) -> int:
    """One sequence's recurrent state (float32) and convolution tails, all
    KDA layers held."""
    s = _sizes(c)
    tails = (s["K"] - 1) * 3 * s["H"] * s["d"]
    return s["n_kda"] * (s["H"] * s["d"] * s["d"] * 4 + tails * BYTES[c["dtype"]])


def experts_touched(c: dict, batch: float) -> float:
    """Held experts of one layer that at least one of ``batch`` tokens picks,
    expected under even routing over all routed experts."""
    share = c["num_experts_per_tok"] / c["published"]["n_routed_experts"]
    return c["n_routed_experts"] * (1.0 - (1.0 - share) ** batch)


def _token_matmul_ops(c: dict) -> float:
    """Multiply-adds x 2 of one token through every held layer's matrices:
    the picks that land here are ``per_token x held / routed`` on average."""
    s = _sizes(c)
    here = c["num_experts_per_tok"] * c["n_routed_experts"] / c["published"]["n_routed_experts"]
    return 2 * (
        s["n_kda"] * s["kda_mm"] + s["n_gqa"] * s["gqa_mm"]
        + s["n_layers"] * (s["router"] + s["shared_mm"] + here * s["expert_mm"])
    )


def _kda_token_ops(c: dict) -> float:
    """The recurrence of one token, all KDA layers and heads: decay the state
    (1 a cell), S'^T k, the rank-one write, S^T q (2 a cell each)."""
    s = _sizes(c)
    return s["n_kda"] * s["H"] * 7 * s["d"] * s["d"]


def _attention_ops(c: dict, pairs: float) -> float:
    """Scores and values over ``pairs`` (query, key) pairs a GQA layer."""
    s = _sizes(c)
    return 2 * s["n_gqa"] * s["Hq"] * 2 * s["Dh"] * pairs


def decode_step(c: dict, batch: float, context_tokens: float, touched: float | None = None):
    """(operations, bytes) of one decode step over ``batch`` sequences whose
    contexts hold ``context_tokens`` positions together. Bytes: every
    non-expert weight and the head once; each held expert that at least one
    token picks (``touched``: their count over all layers as the program's
    counter gave it, or, where no counter was read, expected under even
    routing); each live sequence's state and tails read and written once; the
    live rows of keys and values read and one pair written a sequence."""
    s = _sizes(c)
    if touched is None:
        touched = s["n_layers"] * experts_touched(c, batch)
    ops = (
        batch * (_token_matmul_ops(c) + 2 * s["D"] * c["vocab_size"])
        + _attention_ops(c, context_tokens) + batch * _kda_token_ops(c)
    )
    nbytes = (
        non_expert_weight_bytes(c) + touched * s["expert_mm"] * BYTES[c["param_dtype"]]
        + 2 * batch * state_bytes_per_slot(c) + kv_bytes_per_token(c) * (context_tokens + batch)
    )
    return ops, nbytes


def prefill(c: dict, tokens: int, touched: float | None = None, start: int = 0):
    """(operations, bytes) of prefilling ``tokens`` positions from ``start``
    (0: a fresh prompt; more: a later chunk of one): the head runs on the last
    position only; a query at position ``i`` sees ``i + 1`` keys; the chunk's
    keys and values are written once and those before it read once; the state
    is written once, and read once by a later chunk. ``touched``: the held
    experts the tokens reached, over all layers, as the program counted them
    (None: every one held)."""
    s = _sizes(c)
    end = start + tokens
    tri = lambda n: n * (n + 1) / 2  # noqa: E731
    ops = (
        tokens * (_token_matmul_ops(c) + _kda_token_ops(c)) + 2 * s["D"] * c["vocab_size"]
        + _attention_ops(c, tri(end) - tri(start))
    )
    if touched is None:
        touched = s["n_layers"] * c["n_routed_experts"]
    nbytes = (
        non_expert_weight_bytes(c) + touched * s["expert_mm"] * BYTES[c["param_dtype"]]
        + kv_bytes_per_token(c) * end + state_bytes_per_slot(c) * (2 if start else 1)
    )
    return ops, nbytes
