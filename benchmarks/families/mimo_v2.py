"""The MiMo-V2 family (five layers that attend a sliding window of 128 with a
learned sink to one that attends everything, 8 and 4 key/value heads by the
layer's kind, keys of 192 beside values of 128, routed experts of which this
chip holds its share and no shared one, behind a leading dense layer) as the
benchmark reaches it: served through the paged engine, whose cache for it has a
part a layer kind, each with its kind's own heads, and gives window blocks back
while a request runs. Configurations use the published key names;
``n_routed_experts`` is the count of experts held here from ``expert_offset``,
``published.n_routed_experts`` the router's width; ``hybrid_layer_pattern`` and
``moe_layer_freq`` are of the layers held. The plain reference is
``reference/mimo_v2_ref.py``.

Provides ``model_config``, ``check``, ``shrink``, ``init_params`` and what a
serving family owes the roofline readers: ``decode_step``, ``prefill``,
``weight_bytes``, ``kv_bytes_per_token``, ``attention_decode``,
``experts_touched`` (see README.md, "A family"), **priced by kind and by what
the mathematics needs**: a position costs a key/value head ``(192 + 128) x 2``
= 640 bytes whatever the pool pads (the program stores a key in 256 lanes),
so a padded pool shows as a lower share of the roofline and not as more work
done; a window layer's rows are ``min(context, sliding_window)`` a slot.
"""

from __future__ import annotations

import functools

from benchmarks.flops_bytes import BYTES

DECODE_STEPS = 3
SHORT_TAIL = 9  # the second prompt: one chunk and so many tokens, its second chunk
SHORT_PROMPT = 77  # the second prompt where the mix prefills no prompt in chunks
# (tokens, answer's length) of the requests that run before the compared two:
# the first and the last leave their slots and their blocks of both parts to
# the two; the second stays and shares their steps.
CHURN = ((100, 2), (120, DECODE_STEPS + 8), (90, 3))
# the reference computed so, in the program's place
REFERENCE_ALONE = ("fp8", "bf16", "no_sink", "unscaled_values", "rope_everywhere", "one_theta", "no_window")
CACHE_WRONGED = ("displaced", "swapped_tables")  # the program, its tables wronged while the compared two run
KV_ROWS = 32  # of each compared request, the newest: the decode steps' and the prompt's last
PARTS = ("full", "window")


def model_config(c: dict, traffic: dict):
    import jax.numpy as jnp

    from ray_tpu.models.mimo_v2 import MimoV2Config

    # What the published file says that the program has one way of doing.
    assert c["scoring_func"] == "sigmoid" and c["hidden_act"] == "silu" and c["topk_method"] == "noaux_tc"
    assert c["n_group"] == c["topk_group"] == 1 and not c["n_shared_experts"] and not c["attention_bias"]
    assert not c["tie_word_embeddings"] and c["rope_scaling"]["rope_type"] == "default"
    assert (c["swa_head_dim"], c["swa_v_head_dim"]) == (c["head_dim"], c["v_head_dim"])
    assert c["swa_num_attention_heads"] == c["num_attention_heads"]
    assert c["sliding_window"] == c["sliding_window_size"]
    assert len(c["hybrid_layer_pattern"]) == len(c["moe_layer_freq"]) == c["num_hidden_layers"]
    e = traffic["engine"]
    return MimoV2Config(
        vocab_size=c["vocab_size"],
        d_model=c["hidden_size"],
        layer_pattern=tuple(c["hybrid_layer_pattern"]),
        moe_layers=tuple(c["moe_layer_freq"]),
        n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"],
        swa_n_kv_head=c["swa_num_key_value_heads"],
        head_dim=c["head_dim"],
        v_head_dim=c["v_head_dim"],
        rotary_dim=int(c["partial_rotary_factor"] * c["head_dim"]),
        sliding_window=c["sliding_window"],
        rope_theta=float(c["rope_theta"]),
        swa_rope_theta=float(c["swa_rope_theta"]),
        value_scale=float(c["attention_value_scale"]),
        full_sink=c["add_full_attention_sink_bias"],
        swa_sink=c["add_swa_attention_sink_bias"],
        d_ff=c["intermediate_size"],
        moe_d_ff=c["moe_intermediate_size"],
        n_experts=c["published"]["n_routed_experts"],
        experts_held=c["n_routed_experts"],
        expert_offset=c["expert_offset"],
        experts_per_token=c["num_experts_per_tok"],
        routed_scaling=float(c["routed_scaling_factor"] or 1.0),
        renormalize=c["norm_topk_prob"],
        max_seq=e["max_seq"],
        window_slots=e["max_slots"],
        prefill_span=e.get("prefill_chunk_tokens") or max(e["prefill_buckets"]),
        rms_eps=c["layernorm_epsilon"],
        silent_ids=tuple(c.get("silent_ids", ())),
        dtype=jnp.dtype(c["dtype"]),
        param_dtype=jnp.dtype(c["param_dtype"]),
    )


def init_params(key, cfg):
    from ray_tpu.models import mimo_v2

    return mimo_v2.init_params(key, cfg)


def shrink(c: dict) -> dict:
    """The tiny keys of a CPU rehearsal: a dense layer and four expert
    layers, both kinds of attention with the published ratios of heads and
    widths, a window of 32 (the compared newest rows all lie inside it), four
    of eight experts held (the program draws its weights so that the scores
    keep the deviation they have at the published sizes)."""
    return {
        **c, "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "swa_num_attention_heads": 4, "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
        "head_dim": 24, "swa_head_dim": 24, "v_head_dim": 16, "swa_v_head_dim": 16,
        "moe_intermediate_size": 32, "sliding_window": 32, "sliding_window_size": 32,
        "n_routed_experts": 4, "num_experts_per_tok": 2, "num_hidden_layers": 5,
        "hybrid_layer_pattern": [0, 1, 1, 0, 1], "moe_layer_freq": [0, 1, 1, 1, 1], "vocab_size": 512,
        "published": {**c["published"], "n_routed_experts": 8},
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
    (``CHURN``) and leave blocks in both parts of the pool; then one prompt of
    a length from the mix's own table goes through its chunks (every chunk
    longer than the window, over window blocks that were given back and taken
    again) beside the request that stayed, and one of a chunk and
    ``SHORT_TAIL`` tokens joins it; both decode three steps. Two numbers
    against the reference's full forward over the same weights, a sequence at
    a time:

    - ``logits_rel_err``: the logits the engine samples from (the next token
      is forced on it where it would sample);
    - ``kv_rel_err``: the newest ``KV_ROWS`` rows of keys and values of each
      of the two sequences in the window layers and in the full layers,
      gathered through the block tables the requests were given (a key's 192
      lanes of its row): where they were written, with which rotation and
      value scale, under which kind's heads.

    ``fp8`` and ``bf16`` (every matmul operand rounded so), ``no_sink``,
    ``unscaled_values``, ``rope_everywhere``, ``one_theta`` and ``no_window``
    (``reference/mimo_v2_ref.py`` says what each leaves out) put the reference
    computed that way in the program's place, over the weights the engine
    would draw. The other controls are the program with its tables wronged
    once both compared requests hold a slot and one has decoded a step:
    ``displaced`` (every slot's row of tables shifted by one entry),
    ``swapped_tables`` (the two requests' rows exchanged). ``program`` also reports ``sink_share_pct``, the reference's
    reading of the probability the sinks take of a whole window's row."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import model_build
    from benchmarks.reference import mimo_v2_ref
    from benchmarks.reference.common import rel_err
    from ray_tpu.llm import LLMEngine, SamplingParams
    from ray_tpu.models import mimo_v2

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
    tokens = [rng.integers(0, c["vocab_size"], size=n + K).astype(np.int32) for n in lens]
    newest = [slice(max(n + K - KV_ROWS, 0), n + K) for n in lens]
    Dk = c["head_dim"]

    def reference(weights, **how):
        """(the compared logits, the compared rows of keys and values a part,
        the sinks' share) of both sequences, one after the other."""
        logits, rows, shares = [], {p: [] for p in PARTS}, []
        for toks, n, new in zip(tokens, lens, newest):
            fwd = jax.jit(functools.partial(
                mimo_v2_ref.forward, c=c, inner=True, logits_at=tuple(range(n - 1, n + K)),
                kv_rows=new, **how,
            ))
            got, inner = fwd(weights, jnp.asarray(toks))
            logits.append(got)
            shares.append(inner["sink_share"])
            for part, kv in inner["kv"].items():
                rows[part].append(kv)
        return jnp.concatenate(logits), [jnp.concatenate(rows[p], axis=1) for p in PARTS], jnp.stack(shares)

    llm_config = model_build.llm_config(c, traffic, seed)
    if who in REFERENCE_ALONE:
        weights = mimo_v2.init_params(jax.random.key(llm_config.seed), llm_config.model_config)
        how = {"quant": who} if who in ("fp8", "bf16") else {"wrong": who}
        got, got_kv, _ = reference(weights, **how)
        want, want_kv, _ = reference(weights)
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
    turns = -(-lens[0] // chunk) if chunked else 0  # the long prompt's prefill, a chunk a turn
    for n, (length, answer) in enumerate(CHURN):
        engine.add_request(
            f"churn{n}", rng.integers(0, c["vocab_size"], size=min(length, longest)).tolist(),
            SamplingParams(max_tokens=answer + (turns if n == 1 else 0), stop_token=never),
        )
    while not (engine.requests["churn0"].finished and engine.requests["churn2"].finished):
        engine.step()

    def admit(i):  # two tokens more than are compared: the request is live when its rows are read
        engine.add_request(
            f"r{i}", tokens[i][: lens[i]].tolist(), SamplingParams(max_tokens=K + 3, stop_token=never)
        )
        return engine.requests[f"r{i}"]

    bs = llm_config.kv_block_size
    rows_of, wronged = {}, []

    def newest_rows(i, r):
        """The newest rows of request ``i`` a part, [layers of the kind, rows,
        KH (Dk + Dv)], through the tables the engine's books give it now (few
        blocks leave the device)."""
        tables, new, out = _tables_of(engine, r), newest[i], []
        first = new.start // bs
        for part in PARTS:  # [layers of the kind, blocks, KH, block, lanes]
            blocks = tables[part][first : -(-new.stop // bs)]
            k, v = (
                np.asarray(engine.pool[part][x][:, blocks].astype(jnp.float32)).transpose(0, 1, 3, 2, 4)
                for x in ("k", "v")
            )  # [layers, blocks, block, KH, lanes]
            flat = lambda x: x.reshape(x.shape[0], -1, x.shape[3] * x.shape[4])  # noqa: E731
            kv = np.concatenate([flat(k[..., :Dk]), flat(v)], axis=-1)
            out.append(kv[:, new.start - first * bs : new.stop - first * bs])
        return out

    def step():
        """One turn; then, once both compared requests hold a slot and one has
        run its first decode step, the control's wrong; then a compared
        request's rows, read once its last compared step has run, while it
        still holds its slot and its blocks (a block given back is the next
        taker's to write)."""
        engine.step()
        if who in CACHE_WRONGED and not wronged and len(reqs) == 2 and all(r.slot >= 0 for r in reqs) and any(
            len(seen[r.request_id]) >= 2 for r in reqs
        ):
            a, b = (r.slot for r in reqs)
            if who == "displaced":
                engine.block_tables[:] = np.roll(engine.block_tables, 1, axis=1)
            else:
                engine.block_tables[[a, b]] = engine.block_tables[[b, a]]
            wronged.append(who)
        for i, r in enumerate(reqs):
            if i not in rows_of and len(seen[r.request_id]) > K:
                assert r.slot >= 0 and not r.finished
                rows_of[i] = newest_rows(i, r)

    reqs = [admit(0)]
    if turns > 2:  # the long prompt's chunks but the last two, beside the request that stayed
        while reqs[0].slot < 0 or lens[0] - reqs[0].pf_next > 2 * chunk:
            step()
    reqs.append(admit(1))  # each takes a chunk a turn, by turns; both end within a turn of each other
    while len(rows_of) < len(reqs):
        step()
    assert who == "program" or wronged, "the control's wrong was never applied"
    got = jnp.stack([x for rows in seen.values() for x in rows[: K + 1]])
    lie = [jnp.asarray(np.concatenate([rows_of[i][n] for i in range(len(reqs))], axis=1)) for n in range(len(PARTS))]
    released = engine.stats["window_blocks_released"]
    # The reference beside the weights alone: the engine's pool and programs go first.
    params = engine.params
    engine.pool = None
    del engine
    want, want_kv, shares = reference(params)
    out = {"logits_rel_err": rel_err(got, want), "kv_rel_err": rel_err(lie, want_kv)}
    out["window_blocks_released"] = released
    if who == "program":
        out["sink_share_pct"] = 100.0 * float(jnp.mean(shares))
    return out


# -- operations and bytes that the algorithm needs (flops_bytes.py says what "needs" means)


def _sizes(c: dict) -> dict:
    D, H = c["hidden_size"], c["num_attention_heads"]
    Dk, Dv = c["head_dim"], c["v_head_dim"]
    heads = {"full": c["num_key_value_heads"], "window": c["swa_num_key_value_heads"]}
    n_win = sum(c["hybrid_layer_pattern"])
    n_moe = sum(c["moe_layer_freq"])
    layers = len(c["hybrid_layer_pattern"])
    b = BYTES[c["dtype"]]
    sinks = {"full": c["add_full_attention_sink_bias"], "window": c["add_swa_attention_sink_bias"]}
    return {
        "D": D, "H": H, "Dk": Dk, "Dv": Dv, "n_layers": layers, "n_win": n_win, "n_full": layers - n_win,
        "n_dense": layers - n_moe, "n_moe": n_moe, "window": c["sliding_window"],
        # weights that take part in a matrix multiplication, a layer of each kind: W_q, W_o; W_k, W_v
        "attn_mm": {k: D * H * (Dk + Dv) + D * KH * (Dk + Dv) for k, KH in heads.items()},
        "norms": 2 * D,  # a layer's two
        "sink_bytes": {k: 4 * H * bool(sinks[k]) for k in heads},  # float32, a query head
        "dense_mm": 3 * D * c["intermediate_size"],
        "expert_mm": 3 * D * c["moe_intermediate_size"],
        "router": D * c["published"]["n_routed_experts"],  # float32, with its bias
        # a position's key and value, one layer of the kind, as the mathematics needs them
        "kv_layer": {k: KH * (Dk + Dv) * b for k, KH in heads.items()},
    }


def non_expert_weight_bytes(c: dict) -> int:
    """Every weight a step reads whatever the routing: every layer's attention
    (its sinks among them, float32) and norms, the dense layers' MLP, the
    routers (float32, with their bias), the final norm and the head over the
    vocabulary held. The embedding table is a gather of a few rows and is left
    out; there is no shared expert."""
    s, b = _sizes(c), BYTES[c["param_dtype"]]
    n = (
        s["n_full"] * s["attn_mm"]["full"] + s["n_win"] * s["attn_mm"]["window"]
        + s["n_layers"] * s["norms"] + s["n_dense"] * s["dense_mm"] + s["D"] + s["D"] * c["vocab_size"]
    )
    return (
        n * b + s["n_moe"] * (s["router"] + c["published"]["n_routed_experts"]) * 4
        + s["n_full"] * s["sink_bytes"]["full"] + s["n_win"] * s["sink_bytes"]["window"]
    )


def weight_bytes(c: dict) -> int:
    """All weights held here but the embedding table: what a prefill reads
    whose tokens reach every expert held."""
    s = _sizes(c)
    return non_expert_weight_bytes(c) + s["n_moe"] * c["n_routed_experts"] * s["expert_mm"] * BYTES[c["param_dtype"]]


def kv_bytes_per_token(c: dict) -> int:
    """The key and the value of one position, every layer held, 640 bytes a
    key/value head (a window layer's are given back once the window has
    passed them)."""
    s = _sizes(c)
    return s["n_full"] * s["kv_layer"]["full"] + s["n_win"] * s["kv_layer"]["window"]


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
        s["n_full"] * s["attn_mm"]["full"] + s["n_win"] * s["attn_mm"]["window"]
        + s["n_dense"] * s["dense_mm"] + s["n_moe"] * (s["router"] + here * s["expert_mm"])
    )


def attention_decode(c: dict, rows_full: float, rows_window: float):
    """(operations, bytes) of one decode step's attention alone, the two kinds
    together (either count 0 prices the other kind alone): scores over keys of
    192 and values of 128 for every query head over the rows each layer needs
    (``rows_full`` a full layer: the sum of the live slots' ``position + 1``;
    ``rows_window`` a window layer: the sum of ``min(position + 1,
    sliding_window)``), each row's key and value read once for its kind's
    key/value heads. The sink is one more term of a sum and is not counted."""
    s = _sizes(c)
    rows = s["n_full"] * rows_full + s["n_win"] * rows_window
    nbytes = s["n_full"] * rows_full * s["kv_layer"]["full"] + s["n_win"] * rows_window * s["kv_layer"]["window"]
    return 2 * s["H"] * (s["Dk"] + s["Dv"]) * rows, nbytes


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
    attn = 2 * s["H"] * (s["Dk"] + s["Dv"]) * (s["n_full"] * pairs_full + s["n_win"] * pairs_win)
    ops = tokens * _token_matmul_ops(c) + 2 * s["D"] * c["vocab_size"] + attn
    if touched is None:
        touched = s["n_moe"] * c["n_routed_experts"]
    seen_before = s["n_full"] * start * s["kv_layer"]["full"] + s["n_win"] * min(start, w - 1) * s["kv_layer"]["window"]
    nbytes = (
        non_expert_weight_bytes(c) + touched * s["expert_mm"] * BYTES[c["param_dtype"]]
        + kv_bytes_per_token(c) * tokens + seen_before
    )
    return ops, nbytes
