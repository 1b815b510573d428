"""The AFMoE family (three layers that attend a sliding window to one that
attends everything, gated attention, sandwich norms, routed experts of which
this chip holds its share behind a few dense layers) as the benchmark reaches
it: served through the paged engine, whose cache for it has a part a layer
kind and gives window blocks back while a request runs. Configurations use the
published key names; ``num_experts`` is the count of experts held here from
``expert_offset``, ``published.num_experts`` the router's width;
``layer_types`` the kinds of the layers held, ``num_dense_layers`` how many of
those lead without experts. The plain reference is ``reference/afmoe_ref.py``.

Provides ``model_config``, ``check``, ``shrink``, ``init_params`` and what a
serving family owes the roofline readers: ``decode_step``, ``prefill``,
``weight_bytes``, ``kv_bytes_per_token``, ``attention_decode``,
``experts_touched`` (see README.md, "A family"), with the window counted: a
window layer's bytes and operations over ``min(context, sliding_window)`` a
slot, a chunk's over the keys it may see.
"""

from __future__ import annotations

import functools

from benchmarks.flops_bytes import BYTES

SLIDING = "sliding_attention"
DECODE_STEPS = 3
SHORT_PROMPT = 77  # beside one of the mix's long ones: off any boundary
# (tokens, answer's length) of the requests that run before the compared two:
# the first and the last leave their slots and their blocks of both parts to
# the two; the second stays and shares their steps.
CHURN = ((100, 2), (120, DECODE_STEPS + 8), (90, 3))
REFERENCE_ALONE = ("fp8", "bf16", "no_window", "rope_everywhere", "ungated")  # the reference computed so
CACHE_WRONGED = ("displaced", "swapped_tables")  # the program, its tables wronged after the first decode step
KV_ROWS = 32  # of each compared request, the newest: the decode steps' and the prompt's last


def model_config(c: dict, traffic: dict):
    import jax.numpy as jnp

    from ray_tpu.models.afmoe import AfmoeConfig

    # What the published file says that the program has one way of doing.
    assert c["score_func"] == "sigmoid" and c["hidden_act"] == "silu" and c["rope_scaling"] is None
    assert c["n_group"] == c["topk_group"] == c["num_expert_groups"] == c["num_limited_groups"] == 1
    assert not c["tie_word_embeddings"] and len(c["layer_types"]) == c["num_hidden_layers"]
    e = traffic["engine"]
    return AfmoeConfig(
        vocab_size=c["vocab_size"],
        d_model=c["hidden_size"],
        layer_types=tuple(c["layer_types"]),
        n_dense=c["num_dense_layers"],
        n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"],
        head_dim=c["head_dim"],
        sliding_window=c["sliding_window"],
        rope_theta=float(c["rope_theta"]),
        d_ff=c["intermediate_size"],
        moe_d_ff=c["moe_intermediate_size"],
        n_experts=c["published"]["num_experts"],
        experts_held=c["num_experts"],
        expert_offset=c["expert_offset"],
        experts_per_token=c["num_experts_per_tok"],
        n_shared_experts=c["num_shared_experts"],
        routed_scaling=float(c["route_scale"]),
        renormalize=c["route_norm"],
        mup=c["mup_enabled"],
        max_seq=e["max_seq"],
        window_slots=e["max_slots"],
        prefill_span=e.get("prefill_chunk_tokens") or max(e["prefill_buckets"]),
        rms_eps=c["rms_norm_eps"],
        silent_ids=tuple(c.get("silent_ids", ())),
        dtype=jnp.dtype(c["dtype"]),
        param_dtype=jnp.dtype(c["param_dtype"]),
    )


def init_params(key, cfg):
    from ray_tpu.models import afmoe

    return afmoe.init_params(key, cfg)


def shrink(c: dict) -> dict:
    """The tiny keys of a CPU rehearsal: a dense layer and three expert
    layers, both kinds of attention, a window of 32, four of eight experts
    held."""
    kinds = [SLIDING, SLIDING, "full_attention", SLIDING]
    return {
        **c, "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32, "sliding_window": 32,
        "num_experts": 4, "num_experts_per_tok": 2, "num_hidden_layers": 4, "num_dense_layers": 1,
        "layer_types": kinds, "vocab_size": 512,
        "published": {**c["published"], "num_experts": 8},
    }


def _tables_of(engine, req) -> dict:
    """The block tables ``req`` truly holds, a part of the pool each, from the
    engine's books and not from ``block_tables`` (which a control wrongs)."""
    import numpy as np

    W = engine._table_width
    full, window = np.zeros(W, np.int32), np.zeros(W, np.int32)
    full[: len(req.blocks)] = req.blocks
    w = engine._window
    window[int(w.lo[req.slot]) : int(w.hi[req.slot])] = w._held[req.slot]
    return {"full": full, "window": window}


def check(c: dict, traffic: dict, seed: int, who: str, devices=None) -> dict:
    """``program`` is what the cell times: an ``LLMEngine`` built as the
    replica builds it (the mix's settings, chunked prefill among them, the
    weights its initialiser draws from the seed, selection bias balanced),
    driven by ``add_request`` and ``step``. Three requests run first
    (``CHURN``) and leave blocks in both parts of the pool; then one prompt
    from the long half of the mix's own table (past window + chunk, so it goes
    through its chunks over blocks that were given back and taken again) and
    one of 77 tokens are admitted, prefilled and decoded three steps beside the
    request that stayed. Two numbers against the reference's full forward
    over the same weights, a sequence at a time:

    - ``logits_rel_err``: the logits the engine samples from (the next token
      is forced on it where it would sample);
    - ``kv_rel_err``: the newest ``KV_ROWS`` rows of keys and values of each
      of the two sequences in the window layers and in the full layers,
      gathered through the block tables the requests were given: where they
      were written, and by which rotation.

    ``fp8`` and ``bf16`` (every matmul operand rounded so), ``no_window``
    (sliding layers attend everything), ``rope_everywhere`` (full layers
    rotated too) and ``ungated`` (no ``sigmoid(g)``) put the reference
    computed that way in the program's place, over the weights the engine
    would draw. The other controls are the program with its tables wronged
    after the first decode step: ``displaced`` (every slot's row of tables
    shifted by one entry), ``swapped_tables`` (the two requests' rows
    exchanged)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import model_build
    from benchmarks.reference import afmoe_ref
    from benchmarks.reference.common import rel_err
    from ray_tpu.llm import LLMEngine, SamplingParams
    from ray_tpu.models import afmoe

    if who not in ("program", *REFERENCE_ALONE, *CACHE_WRONGED):
        raise SystemExit(f"unknown --who {who!r}")
    K = DECODE_STEPS
    e = traffic["engine"]
    rng = np.random.default_rng(seed)
    longest = max(e["prefill_buckets"]) - K - 1
    past = c["sliding_window"] + (e.get("prefill_chunk_tokens") or 0)
    long = [p for p in traffic["prompt_tokens"] if p >= past] or [max(traffic["prompt_tokens"])]
    lens = [min(int(rng.choice(long)), longest), min(SHORT_PROMPT, longest)]
    tokens = [rng.integers(0, c["vocab_size"], size=n + K).astype(np.int32) for n in lens]
    newest = [slice(max(n + K - KV_ROWS, 0), n + K) for n in lens]

    def reference(weights, **how):
        """(the compared logits, the compared rows of keys and values a part)
        of both sequences, one after the other."""
        logits, rows = [], {"full": [], "window": []}
        for toks, n, new in zip(tokens, lens, newest):
            fwd = jax.jit(functools.partial(
                afmoe_ref.forward, c=c, inner=True, logits_at=tuple(range(n - 1, n + K)),
                kv_rows=new, **how,
            ))
            got, inner = fwd(weights, jnp.asarray(toks))
            logits.append(got)
            for part, kv in inner["kv"].items():
                rows[part].append(kv)
        return jnp.concatenate(logits), [jnp.concatenate(rows[p], axis=1) for p in ("full", "window")]

    llm_config = model_build.llm_config(c, traffic, seed)
    if who in REFERENCE_ALONE:
        weights = afmoe.init_params(jax.random.key(llm_config.seed), llm_config.model_config)
        how = {"quant": who} if who in ("fp8", "bf16") else {"wrong": who}
        got, got_kv = reference(weights, **how)
        want, want_kv = reference(weights)
        return {"logits_rel_err": rel_err(got, want), "kv_rel_err": rel_err(got_kv, want_kv)}

    engine = LLMEngine(llm_config)
    seen: dict = {f"r{i}": [] for i in range(len(lens))}

    def forced(logits, req):  # where the engine would sample: note the logits, force the token
        rows = seen.get(req.request_id)
        if rows is None:
            return 1  # a churn request: any token that is not its stop token
        rows.append(np.array(logits))
        i, j = int(req.request_id[1:]), len(rows) - 1
        return int(tokens[i][lens[i] + j]) if j < K else 0

    engine._sample = forced
    never = -1  # no token stops a request: each runs its max_tokens
    chunk = llm_config.prefill_chunk_tokens
    turns = -(-lens[0] // chunk) if chunk else 0  # the long prompt's prefill, a chunk a turn
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

    reqs = [admit(0)]
    if turns > 1:  # the long prompt's chunks but the last, beside the request that stayed
        while reqs[0].slot < 0 or lens[0] - reqs[0].pf_next > chunk:
            engine.step()
    reqs.append(admit(1))
    engine.step()  # the long prompt's last chunk, the short prompt whole, the first decode step of the three
    a, b = (r.slot for r in reqs)
    assert min(a, b) >= 0 and all(len(rows) == 2 for rows in seen.values()), (a, b)
    if who == "displaced":
        engine.block_tables[:] = np.roll(engine.block_tables, 1, axis=1)
    elif who == "swapped_tables":
        engine.block_tables[[a, b]] = engine.block_tables[[b, a]]
    given = [_tables_of(engine, r) for r in reqs]
    while not all(r.finished for r in reqs):
        engine.step()
        for i, r in enumerate(reqs):  # a window block taken at a later step
            if r.slot >= 0:
                given[i] = _tables_of(engine, r)
    got = jnp.stack([x for rows in seen.values() for x in rows[: K + 1]])
    want, want_kv = reference(engine.params)
    out = {"logits_rel_err": rel_err(got, want)}
    bs = llm_config.kv_block_size
    lie = []
    for part in ("full", "window"):  # [layers of the kind, blocks, KH, block, Dh]; few blocks leave the device
        rows = []
        for i, new in enumerate(newest):
            first = new.start // bs
            blocks = given[i][part][first : -(-new.stop // bs)]
            k, v = (
                np.asarray(engine.pool[part][x][:, blocks].astype(jnp.float32)).transpose(0, 1, 3, 2, 4)
                for x in ("k", "v")
            )  # [layers, blocks, block, KH, Dh]
            flat = lambda x: x.reshape(x.shape[0], -1, x.shape[3] * x.shape[4])  # noqa: E731
            kv = np.concatenate([flat(k), flat(v)], axis=-1)
            rows.append(kv[:, new.start - first * bs : new.stop - first * bs])
        lie.append(jnp.asarray(np.concatenate(rows, axis=1)))
    out["kv_rel_err"] = rel_err(lie, want_kv)
    out["window_blocks_released"] = engine.stats["window_blocks_released"]
    return out


# -- operations and bytes that the algorithm needs (flops_bytes.py says what "needs" means)


def _sizes(c: dict) -> dict:
    D, H, KH, Dh = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    layers = len(c["layer_types"])
    n_win = sum(kind == SLIDING for kind in c["layer_types"])
    return {
        "D": D, "H": H, "KH": KH, "Dh": Dh, "n_layers": layers, "n_win": n_win, "n_full": layers - n_win,
        "n_dense": c["num_dense_layers"], "n_moe": layers - c["num_dense_layers"],
        "window": c["sliding_window"],
        # weights that take part in a matrix multiplication, per layer of a kind
        "attn_mm": 3 * D * H * Dh + 2 * D * KH * Dh,  # W_q, W_g, W_o; W_k, W_v
        "attn_other": 2 * Dh + 4 * D,  # the heads' two norms, the sandwich's four
        "dense_mm": 3 * D * c["intermediate_size"],
        "expert_mm": 3 * D * c["moe_intermediate_size"],
        "shared_mm": 3 * D * c["moe_intermediate_size"] * c["num_shared_experts"],
        "router": D * c["published"]["num_experts"],  # float32, with its bias
        "kv_layer": 2 * KH * Dh * BYTES[c["dtype"]],  # a position's key and value, one layer
    }


def non_expert_weight_bytes(c: dict) -> int:
    """Every weight a step reads whatever the routing: every layer's attention
    and norms, the dense layers' MLP, routers (float32, with their bias) and
    shared experts, the final norm and the head over the vocabulary held. The
    embedding table is a gather of a few rows and is left out."""
    s, b = _sizes(c), BYTES[c["param_dtype"]]
    n = (
        s["n_layers"] * (s["attn_mm"] + s["attn_other"]) + s["n_dense"] * s["dense_mm"]
        + s["n_moe"] * s["shared_mm"] + s["D"] + s["D"] * c["vocab_size"]
    )
    return n * b + s["n_moe"] * (s["router"] + c["published"]["num_experts"]) * 4


def weight_bytes(c: dict) -> int:
    """All weights held here but the embedding table: what a prefill reads
    whose tokens reach every expert held."""
    s = _sizes(c)
    return non_expert_weight_bytes(c) + s["n_moe"] * c["num_experts"] * s["expert_mm"] * BYTES[c["param_dtype"]]


def kv_bytes_per_token(c: dict) -> int:
    """The key and the value of one position, every layer held (a window
    layer's are given back once the window has passed them)."""
    s = _sizes(c)
    return s["n_layers"] * s["kv_layer"]


def experts_touched(c: dict, batch: float) -> float:
    """Held experts of one layer that at least one of ``batch`` tokens picks,
    expected under even routing over all routed experts."""
    share = c["num_experts_per_tok"] / c["published"]["num_experts"]
    return c["num_experts"] * (1.0 - (1.0 - share) ** batch)


def _token_matmul_ops(c: dict) -> float:
    """Multiply-adds x 2 of one token through every held layer's matrices:
    the picks that land here are ``per_token x held / routed`` on average."""
    s = _sizes(c)
    here = c["num_experts_per_tok"] * c["num_experts"] / c["published"]["num_experts"]
    return 2 * (
        s["n_layers"] * s["attn_mm"] + s["n_dense"] * s["dense_mm"]
        + s["n_moe"] * (s["router"] + s["shared_mm"] + here * s["expert_mm"])
    )


def attention_decode(c: dict, rows_full: float, rows_window: float):
    """(operations, bytes) of one decode step's attention alone: scores and
    values over the rows each layer needs (``rows_full`` a full layer: the sum
    of the live slots' ``position + 1``; ``rows_window`` a window layer: the
    sum of ``min(position + 1, sliding_window)``), each row's key and value
    read once."""
    s = _sizes(c)
    rows = s["n_full"] * rows_full + s["n_win"] * rows_window
    return 2 * s["H"] * 2 * s["Dh"] * rows, s["kv_layer"] * rows


def decode_step(c: dict, batch: float, context_tokens: float, touched: float | None = None,
                rows_window: float | None = None):
    """(operations, bytes) of one decode step over ``batch`` sequences whose
    contexts hold ``context_tokens`` positions together. ``rows_window``: the
    rows a window layer needs, ``min(context, sliding_window)`` summed over
    the sequences (None: every sequence at the mean context). Bytes: every
    non-expert weight and the head once; each held expert that at least one
    token picks (``touched``: their count over all expert layers as the
    program's counter gave it, or, where no counter was read, expected under
    even routing); the rows of keys and values each layer needs read, and one
    pair written a sequence and layer."""
    s = _sizes(c)
    if touched is None:
        touched = s["n_moe"] * experts_touched(c, batch)
    if rows_window is None:
        rows_window = batch * min(context_tokens / max(batch, 1), s["window"])
    attn_ops, attn_bytes = attention_decode(c, context_tokens, rows_window)
    ops = batch * (_token_matmul_ops(c) + 2 * s["D"] * c["vocab_size"]) + attn_ops
    nbytes = (
        non_expert_weight_bytes(c) + touched * s["expert_mm"] * BYTES[c["param_dtype"]]
        + attn_bytes + kv_bytes_per_token(c) * batch
    )
    return ops, nbytes


def prefill(c: dict, tokens: int, touched: float | None = None, start: int = 0):
    """(operations, bytes) of prefilling ``tokens`` positions from ``start``
    (0: a fresh prompt; more: a later chunk of one): the head runs on the last
    position only; a query at position ``i`` sees ``i + 1`` keys in a full
    layer and ``min(i + 1, sliding_window)`` in a window layer; the chunk's
    keys and values are written once and those before it that it may see are
    read once. ``touched``: the held experts the tokens reached, over all
    expert layers, as the program counted them (None: every one held)."""
    s = _sizes(c)
    w = s["window"]
    end = start + tokens
    tri = lambda n: n * (n + 1) / 2  # noqa: E731
    pairs_full = tri(end) - tri(start)
    inside = lambda n: tri(min(n, w)) + w * max(n - w, 0)  # noqa: E731: sum of min(i + 1, w) over i < n
    pairs_win = inside(end) - inside(start)
    attn = 2 * s["H"] * 2 * s["Dh"] * (s["n_full"] * pairs_full + s["n_win"] * pairs_win)
    ops = tokens * _token_matmul_ops(c) + 2 * s["D"] * c["vocab_size"] + attn
    if touched is None:
        touched = s["n_moe"] * c["num_experts"]
    seen_before = s["n_full"] * start + s["n_win"] * min(start, w - 1)
    nbytes = (
        non_expert_weight_bytes(c) + touched * s["expert_mm"] * BYTES[c["param_dtype"]]
        + kv_bytes_per_token(c) * tokens + s["kv_layer"] * seen_before
    )
    return ops, nbytes
