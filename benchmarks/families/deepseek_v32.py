"""The ``deepseek_v32`` family (``mla_moe``'s block behind a lightning indexer:
64 small heads score every cached position, the best ``index_topk`` are kept
a query, and latent attention runs over those rows alone) as the benchmark
reaches it: served through the paged engine, an index-key pool beside the
latent pool under one block table. Configurations use the published key
names; ``n_routed_experts`` is the count of experts held here from
``expert_offset``, ``published.n_routed_experts`` the router's width. The
plain reference is ``reference/deepseek_v32_ref.py``.

Provides ``model_config``, ``check``, ``shrink``, ``init_params`` and what a
serving family owes the roofline readers: ``decode_step``, ``prefill``,
``weight_bytes``, ``kv_bytes_per_token`` (see README.md, "A family"). The
counts are of what the mathematics needs: index scores over all of a
context, attention over ``min(context, index_topk)`` rows of it.
"""

from __future__ import annotations

import functools

from benchmarks.flops_bytes import BYTES

DECODE_STEPS = 3
SHORT_PROMPT = 77  # beside one of the mix's own lengths: its own bucket, off any boundary
# The long prompt is drawn from the mix's lengths up to this: the float32
# reference attends every pair of positions of every layer before it selects
# (at ``highest`` precision, six passes a product): 24,576 costs 2.2 times a
# 16k one (134 s a row against 60), the mix's longest, 32,768, more again, and
# every run of the cell makes this check once, in 900 s.
LONGEST_CHECKED = 16384
# (tokens, answer's length) of the requests that run before the compared two:
# the first and the last leave their slots and blocks to the two; the second
# stays and shares their steps.
CHURN = ((100, 2), (120, DECODE_STEPS + 8), (90, 3))
# The program: as every run of the cell compares it, and with the mix's longest
# prompt for the long one (check.py alone: past LONGEST_CHECKED).
PROGRAM = ("program", "longest")
REFERENCE_ALONE = ("fp8", "bf16", "dense", "recent")  # the reference, computed wrongly on purpose
CACHE_WRONGED = ("displaced", "swapped_tables")  # the engine, its tables wronged once both requests decode
POOL_ROWS = 32  # of each compared request, the newest: the decode steps' and the prompt's last


def model_config(c: dict, traffic: dict):
    import jax.numpy as jnp

    from ray_tpu.models.deepseek_v32 import DeepseekV32Config

    # What the published file says that the program has one way of doing.
    assert c["scoring_func"] == "sigmoid" and c["hidden_act"] == "silu" and c["moe_layer_freq"] == 1
    assert c["topk_method"] == "noaux_tc" and not c["attention_bias"] and not c["tie_word_embeddings"]
    rs = c["rope_scaling"]
    assert rs["type"] == "yarn"
    return DeepseekV32Config(
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
        index_n_heads=c["index_n_heads"],
        index_head_dim=c["index_head_dim"],
        index_topk=c["index_topk"],
        index_norm_eps=c["index_norm_eps"],
        max_seq=traffic["engine"]["max_seq"],
        rms_eps=c["rms_norm_eps"],
        dtype=jnp.dtype(c["dtype"]),
        param_dtype=jnp.dtype(c["param_dtype"]),
    )


def init_params(key, cfg):
    from ray_tpu.models import deepseek_v32

    return deepseek_v32.init_params(key, cfg)


def shrink(c: dict) -> dict:
    """The tiny keys of a CPU rehearsal: ``mla_moe``'s (a dense layer and two
    expert layers; sixteen experts in two groups, the first four held here;
    YaRN stretched from an original context of 32) and four index heads of 16
    that keep 16 positions, so that the rehearsal's prompts select."""
    return {
        **c, "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 2,
        "num_key_value_heads": 2, "q_lora_rank": 24, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "moe_intermediate_size": 32, "n_routed_experts": 4, "num_experts_per_tok": 2,
        "n_group": 2, "topk_group": 1, "num_hidden_layers": 3, "vocab_size": 512,
        "index_n_heads": 4, "index_head_dim": 16, "index_topk": 16,
        "rope_scaling": {**c["rope_scaling"], "factor": 8, "original_max_position_embeddings": 32},
        "published": {**c["published"], "n_routed_experts": 16},
    }


def _agreement(a_and_b: int, a: int, b: int) -> float:
    """100 x the positions both selected over those either did."""
    return 100.0 * a_and_b / max(a + b - a_and_b, 1)


def check(c: dict, traffic: dict, seed: int, who: str, devices=None) -> dict:
    """``program`` is what the cell times: an ``LLMEngine`` built as the
    replica builds it (the mix's settings, chunked prefill among them, the
    weights its initialiser draws from the seed, routers centred), driven by
    ``add_request`` and ``step``. Three requests run first (``CHURN``); then
    one prompt of a length from the mix's own table (up to
    ``LONGEST_CHECKED``: selection keeps ``index_topk`` of 8k-16k positions)
    goes through its chunks beside the request that stayed, one of 77 tokens
    joins it before its last chunk, and both decode three steps, logits
    forced. Against the reference's full forward over the same weights, a
    sequence at a time (it runs first, on the weights alone, and the engine
    is then handed those weights: the reference's float32 activations over
    16k positions do not fit beside the pool):

    - ``logits_rel_err``: the logits the engine samples from;
    - ``latent_rel_err``, ``index_key_rel_err``: the newest ``POOL_ROWS`` rows
      of each of the two sequences as they lie in the two parts of the pool
      afterwards, gathered through the block table the request was given,
      against the reference's ``[c^; R_t k_r]`` and rotated index key at those
      positions: where rows were written, and by which rotation;
    - ``select_agree_pct``, and ``select_miss_pct`` = 100 less it, which is
      what a limit can hold: over (layer, query) of the long prompt's last
      chunk and of both sequences' decode steps, the positions that program
      and reference both selected over those that either did. The program's
      are read by running the last chunk and the three steps once more over
      the pool as the engine left it (the engine's programs do not return
      them), through the tables the requests were given;
    - ``route_agree_pct`` (``program`` alone): the share of the last chunk's
      (token, expert layer, pick) choices on which program and reference agree.

    ``longest`` is ``program`` with the mix's longest prompt as the long one.

    ``fp8`` and ``bf16`` (every product's operands rounded so), ``dense``
    (step 4 left out: every position attended) and ``recent`` (the newest
    ``index_topk`` positions in place of the top) put the reference computed
    that way in the program's place, over the weights the engine would draw.
    ``displaced`` (every block table shifted by one entry) and
    ``swapped_tables`` (the two requests' tables exchanged) are the program
    with its cache wronged once both requests have decoded a step; one table
    serves both parts of the pool, so both are wronged."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import model_build
    from benchmarks.reference import deepseek_v32_ref
    from benchmarks.reference.common import rel_err
    from ray_tpu.llm import LLMEngine, SamplingParams
    from ray_tpu.models import deepseek_v32

    if who not in (*PROGRAM, *REFERENCE_ALONE, *CACHE_WRONGED):
        raise SystemExit(f"unknown --who {who!r}")
    K = DECODE_STEPS
    e = traffic["engine"]
    rng = np.random.default_rng(seed)
    longest = max(e["prefill_buckets"]) - K - 1
    chunk = e.get("prefill_chunk_tokens") or 0
    table = [n for n in traffic["prompt_tokens"] if n <= LONGEST_CHECKED] or [min(traffic["prompt_tokens"])]
    if who == "longest":
        table = [max(traffic["prompt_tokens"])]
    lens = [min(int(rng.choice(table)), longest), min(SHORT_PROMPT, longest)]
    chunked = 0 < chunk < lens[0]
    first = (lens[0] - 1) // chunk * chunk if chunked else 0  # where the long prompt's last chunk begins
    tokens = [rng.integers(0, c["vocab_size"], size=n + K).astype(np.int32) for n in lens]
    newest = [slice(max(n + K - POOL_ROWS, 0), n + K) for n in lens]
    compared = [slice(first, lens[0] + K), slice(0, lens[1] + K)]  # the queries whose selections are compared
    topk = c["index_topk"]

    def reference(weights, **how):
        """Of both sequences, one after the other, on the host: the compared
        logits, the newest rows of both pool parts, the selected positions of
        the compared queries ([layers, queries, k], -1 none; None for a
        variant that does not select by score) and the long prompt's picks."""
        out = {"logits": [], "latents": [], "index_keys": [], "selected": [], "picks": None}
        for toks, n, new, rows in zip(tokens, lens, newest, compared):
            fwd = jax.jit(functools.partial(
                deepseek_v32_ref.forward, c=c, inner=True, logits_at=tuple(range(n - 1, n + K)), **how,
            ))
            logits, inner = fwd(weights, jnp.asarray(toks))
            out["logits"].append(np.asarray(logits))
            out["latents"].append(np.asarray(inner["latents"][:, new]))
            out["index_keys"].append(np.asarray(inner["index_keys"][:, new]))
            out["selected"].append(np.asarray(inner["selected"][:, rows]) if "selected" in inner else None)
            if out["picks"] is None:
                out["picks"] = np.asarray(inner["picks"][:, first:n])
            del logits, inner
        return out

    def errors(got, want):
        cat = np.concatenate
        return {
            "logits_rel_err": rel_err(cat(got["logits"]), cat(want["logits"])),
            "latent_rel_err": rel_err(cat(got["latents"], 1), cat(want["latents"], 1)),
            "index_key_rel_err": rel_err(cat(got["index_keys"], 1), cat(want["index_keys"], 1)),
        }

    def selection(agree):
        return {"select_agree_pct": agree, "select_miss_pct": 100.0 - agree}

    llm_config = model_build.llm_config(c, traffic, seed)
    cfg, bs = llm_config.model_config, llm_config.kv_block_size
    weights = deepseek_v32.init_params(jax.random.key(llm_config.seed), cfg)
    want = reference(weights)
    if who in REFERENCE_ALONE:
        got = reference(weights, **({"quant": who} if who in ("fp8", "bf16") else {"variant": who}))
        both = mine = theirs = 0
        for sel, ctl, rows in zip(want["selected"], got["selected"], compared):
            at = np.arange(rows.start, rows.stop)[None, :, None]  # a query's position
            mine += int((sel >= 0).sum())
            if who == "dense":  # every position up to the query's own
                theirs += sel.shape[0] * int((at + 1).sum())
                both += int((sel >= 0).sum())
            elif who == "recent":
                theirs += sel.shape[0] * int(np.minimum(at + 1, topk).sum())
                both += int(((sel >= 0) & (sel > at - topk)).sum())
            else:
                theirs += int((ctl >= 0).sum())
                both += sum(
                    len(np.intersect1d(a[a >= 0], b[b >= 0], assume_unique=True))
                    for a, b in zip(sel.reshape(-1, sel.shape[-1]), ctl.reshape(-1, ctl.shape[-1]))
                )
        return {**errors(got, want), **selection(_agreement(both, mine, theirs))}

    gc.collect()
    # The engine draws its weights from the seed (deepseek_v32.init_params),
    # which are these: handing them over keeps one copy of 7.6 GB alive, not two.
    drawn, deepseek_v32.init_params = deepseek_v32.init_params, lambda key, cfg: weights
    try:
        engine = LLMEngine(llm_config)
    finally:
        deepseek_v32.init_params = drawn
    seen: dict = {f"r{i}": [] for i in range(len(lens))}
    given: dict = {}  # request -> the table it was given, before any is wronged
    wronged = False

    def forced(logits, req):  # where the engine would sample: note the logits, force the token
        rows = seen.get(req.request_id)
        if rows is None:
            return 1  # a churn request: any token that is not its stop token
        rows.append(np.array(logits))
        i, j = int(req.request_id[1:]), len(rows) - 1
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
        """One turn; then each compared request's table as the engine's books
        have it, and, for a wronged control, the tables wronged once both
        requests have decoded a step."""
        nonlocal wronged
        engine.step()
        for r in reqs:
            if r.slot >= 0 and not wronged:
                given[r.request_id] = engine.block_tables[r.slot].copy()
        if who in CACHE_WRONGED and not wronged and all(len(seen[r.request_id]) >= 2 for r in reqs):
            slots = [r.slot for r in reqs if r.slot >= 0]
            assert slots, "both requests ended before the tables could be wronged"
            if who == "displaced":
                engine.block_tables[:] = np.roll(engine.block_tables, 1, axis=1)
            elif len(slots) == 2:
                engine.block_tables[slots] = engine.block_tables[slots[::-1]]
            wronged = True

    reqs = [admit(0)]
    while chunked and (reqs[0].slot < 0 or lens[0] - reqs[0].pf_next > chunk):
        step()  # the long prompt's chunks but the last, beside the request that stayed
    reqs.append(admit(1))  # both sample their first token within a turn or two of each other
    while not all(r.finished for r in reqs):
        step()
    tables = np.stack([given[r] for r in seen])
    pool = engine.pool
    got = {"logits": [np.stack(rows[: K + 1]) for rows in seen.values()], "latents": [], "index_keys": []}
    for part, name in (("ckv", "latents"), ("ikv", "index_keys")):
        width = want[name][0].shape[-1]
        for i, rows in enumerate(newest):  # only the two tables' blocks leave the device
            lie = pool[part][:, tables[i]].reshape(pool[part].shape[0], -1, pool[part].shape[-1])
            got[name].append(np.asarray(lie[:, rows, :width].astype(jnp.float32)))
    out = errors(got, want)

    # The selections: the long prompt's last chunk and the steps once more,
    # over the pool as it lies (each rewrites the rows it wrote before).
    n = lens[0]
    bucket = chunk if chunked else min(x for x in llm_config.prefill_buckets if x >= n)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, : n - first] = tokens[0][first:n]
    engine.pool = None
    pool, _, _, picks, kept = jax.jit(
        functools.partial(deepseek_v32.paged_prefill, cfg=cfg, block_size=bs, with_picks=True, with_selection=True),
        donate_argnums=5,
    )(weights, jnp.asarray(toks), jnp.asarray(n - first, jnp.int32), jnp.asarray(first, jnp.int32),
      jnp.asarray(tables[0]), pool)
    sel = want["selected"][0][:, : n - first]  # [layers, queries, k]
    hit = jnp.take_along_axis(kept[:, : n - first], jnp.asarray(np.maximum(sel, 0)), axis=2) & jnp.asarray(sel >= 0)
    both, mine, theirs = int(hit.sum()), int(kept[:, : n - first].sum()), int((sel >= 0).sum())
    same = np.sort(np.asarray(picks[:, : n - first]), -1) == np.sort(want["picks"], -1)
    del kept, hit
    decode = jax.jit(
        functools.partial(deepseek_v32.paged_decode, cfg=cfg, block_size=bs, with_selection=True),
        donate_argnums=4,
    )
    for j in range(K):
        last = np.asarray([tokens[i][lens[i] + j] for i in range(len(lens))], np.int32)
        at = np.asarray([m + j for m in lens], np.int32)
        pool, _, _, (idx, keep) = decode(weights, jnp.asarray(last), jnp.asarray(at), jnp.asarray(tables), pool)
        idx = np.where(np.asarray(keep), np.asarray(idx), -1)  # [layers, 2, k]
        for i, m in enumerate(lens):
            ref_rows = want["selected"][i][:, m + j - compared[i].start]
            for a, b in zip(idx[:, i], ref_rows):
                a, b = a[a >= 0], b[b >= 0]
                both, mine, theirs = both + len(np.intersect1d(a, b, assume_unique=True)), mine + len(a), theirs + len(b)
    engine.pool = pool
    out.update(selection(_agreement(both, mine, theirs)))
    if who in PROGRAM:
        out["route_agree_pct"] = 100.0 * float(same.mean())
    return out


# -- operations and bytes that the algorithm needs (flops_bytes.py says what "needs" means)


def _sizes(c: dict) -> dict:
    D, H = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv, R = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"]
    rq, J, Di = c["q_lora_rank"], c["index_n_heads"], c["index_head_dim"]
    query = D * rq + rq * H * (dn + dr)
    dense = c["first_k_dense_replace"]
    return {
        "D": D, "H": H, "dn": dn, "dr": dr, "dv": dv, "R": R, "J": J, "Di": Di, "topk": c["index_topk"],
        "n_layers": c["num_hidden_layers"], "n_dense": dense,
        "n_moe": c["num_hidden_layers"] - dense,
        # weights that take part in a matrix multiplication, per layer of a kind
        "mla_mm": query + D * (R + dr) + R * H * (dn + dv) + H * dv * D,
        "index_mm": rq * J * Di + D * Di + D * J,
        "attn_other": R + rq + 2 * Di,  # kv_norm, q_norm, the index key's LayerNorm
        "dense_mm": 3 * D * c["intermediate_size"],
        "expert_mm": 3 * D * c["moe_intermediate_size"],
        "shared_mm": 3 * D * c["moe_intermediate_size"] * c["n_shared_experts"],
        "router": D * c["published"]["n_routed_experts"] + c["published"]["n_routed_experts"],  # float32, and its bias
    }


def non_expert_weight_bytes(c: dict) -> int:
    """Every weight a step reads whatever the routing: the latent attention
    and the indexer of every layer, the dense layer's MLP, routers with their
    selection bias (float32) and shared experts, both norms of each layer,
    the final norm and the head over the vocabulary held. The embedding table
    is a gather of a few rows and is left out."""
    s, b = _sizes(c), BYTES[c["param_dtype"]]
    n = (
        s["n_layers"] * (s["mla_mm"] + s["index_mm"] + s["attn_other"] + 2 * s["D"])
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
    """The latent row and the index key of one position, every layer."""
    s = _sizes(c)
    return s["n_layers"] * (s["R"] + s["dr"] + s["Di"]) * BYTES[c["dtype"]]


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
        s["n_layers"] * (s["mla_mm"] + s["index_mm"]) + s["n_dense"] * s["dense_mm"]
        + s["n_moe"] * (s["router"] + s["shared_mm"] + here * s["expert_mm"])
    )


def _index_pair_ops(c: dict) -> float:
    """Of one (query, key) pair in one layer: ``J`` products over ``d_I`` and
    their weighted sum."""
    s = _sizes(c)
    return 2 * s["J"] * (s["Di"] + 1)


def decode_step(c: dict, batch: float, context_tokens: float, touched: float | None = None):
    """(operations, bytes) of one decode step over ``batch`` sequences whose
    contexts hold ``context_tokens`` positions together. The indexer scores
    every live position (its index key read once); attention reads the
    selected rows alone, ``min(context, index_topk)`` a sequence, taken here as
    ``min(context_tokens, batch x index_topk)`` (the same wherever every
    context is past ``index_topk`` or none is; an upper bound between).
    Bytes: every non-expert weight and the head once; each held expert that
    at least one token picks (``touched``: as the program's counter gave it,
    or expected under even routing); the index keys scored, the latent rows
    selected, and one of each written a sequence. Operations: the matrices,
    the index scores, and the absorbed attention, ``2 H (2 r_kv + d_r)`` a
    selected row and layer."""
    s = _sizes(c)
    if touched is None:
        touched = s["n_moe"] * experts_touched(c, batch)
    selected = min(context_tokens, batch * s["topk"])
    matmul = batch * (_token_matmul_ops(c) + 2 * s["D"] * c["vocab_size"])
    index = s["n_layers"] * _index_pair_ops(c) * context_tokens
    attn = 2 * s["n_layers"] * s["H"] * (2 * s["R"] + s["dr"]) * selected
    b = BYTES[c["dtype"]]
    nbytes = (
        non_expert_weight_bytes(c)
        + touched * s["expert_mm"] * BYTES[c["param_dtype"]]
        + s["n_layers"] * b * (s["Di"] * context_tokens + (s["R"] + s["dr"]) * selected)
        + kv_bytes_per_token(c) * batch
    )
    return matmul + index + attn, nbytes


def _pairs(start: int, tokens: int, topk: int | None = None) -> float:
    """(query, key) pairs of ``tokens`` queries from position ``start``, each
    seeing its own position and all before it, or ``topk`` of them at most."""
    tri = lambda n: n * (n + 1) / 2  # noqa: E731
    end = start + tokens
    if topk is None:
        return tri(end) - tri(start)
    below = max(min(end, topk) - start, 0)  # queries that see topk positions or fewer
    return tri(start + below) - tri(start) + (tokens - below) * topk


def prefill(c: dict, tokens: int, touched: float | None = None, start: int = 0):
    """(operations, bytes) of prefilling ``tokens`` positions from ``start``
    (0: a fresh prompt; more: a later chunk of one): the head runs on the last
    position only; a query at position ``i`` scores ``i + 1`` index keys and
    attends ``min(i + 1, index_topk)`` rows, keys and values expanded per head;
    the chunk's rows and index keys are written once, the index keys before
    it read once and, of the latent rows before it, those that some query
    selected: at most all of them, and at most ``tokens x index_topk``.
    ``touched``: the held experts the tokens reached, over all layers, as the
    program counted them (None: every one held)."""
    s = _sizes(c)
    ops = (
        tokens * _token_matmul_ops(c) + 2 * s["D"] * c["vocab_size"]
        + s["n_layers"] * _index_pair_ops(c) * _pairs(start, tokens)
        + 2 * s["n_layers"] * s["H"] * (s["dn"] + s["dr"] + s["dv"]) * _pairs(start, tokens, s["topk"])
    )
    if touched is None:
        touched = s["n_moe"] * c["n_routed_experts"]
    b = BYTES[c["dtype"]]
    rows_read = min(start, tokens * s["topk"])
    nbytes = (
        non_expert_weight_bytes(c) + touched * s["expert_mm"] * BYTES[c["param_dtype"]]
        + kv_bytes_per_token(c) * tokens
        + s["n_layers"] * b * (s["Di"] * start + (s["R"] + s["dr"]) * rows_read)
    )
    return ops, nbytes


def selected_attention(c: dict, tokens: int, start: int = 0):
    """(operations, bytes) of a prefill's attention alone, every layer, as the
    mathematics needs it (what the kernel ``selected_attention_fold`` is held
    against): a query at position ``i`` attends ``min(i + 1, index_topk)``
    rows, scores over ``d_n + d_r`` and values over ``d_v`` a head; each
    query's heads read and their output written once, and of the latent rows
    up to the chunk's end those that some query selected, at most all of them
    and at most ``tokens x index_topk``. The kernel computes every position
    of every stretch under the mask, so its share of this falls with the
    context: that is the finding, not a fault of the count."""
    s = _sizes(c)
    b = BYTES[c["dtype"]]
    width = s["dn"] + s["dr"] + s["dv"]
    ops = 2 * s["n_layers"] * s["H"] * width * _pairs(start, tokens, s["topk"])
    rows = min(start + tokens, tokens * s["topk"])
    return ops, s["n_layers"] * b * (tokens * s["H"] * width + rows * (s["R"] + s["dr"]))
