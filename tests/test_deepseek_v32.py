"""The ``deepseek_v32`` family (``mla_moe``'s block behind a lightning indexer
that keeps ``index_topk`` positions a query) on the CPU at a tiny size in
float32, with ``index_topk`` 16 well under the contexts so that selection is
at work, against the plain reference (benchmarks/reference/deepseek_v32_ref.py,
which imports nothing of the program).

Tolerances as tests/test_mla_moe.py's: float32 on both sides, 2e-4 relative
with an absolute floor of a few 1e-6. The selected sets are compared exactly:
both sides score in float32, and a pick flipped by the order of a sum would
show as a logit off by far more than the tolerance, so a seed on which two
scores tie to the last bit would have to be replaced, not tolerated.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import deepseek_v32_ref as ref  # noqa: E402
from ray_tpu.models import deepseek_v32 as dv, mla_moe, paged  # noqa: E402
from test_mla_moe import ref_config as mla_ref_config  # noqa: E402


def ref_config(cfg: dv.DeepseekV32Config) -> dict:
    return dict(
        mla_ref_config(cfg), index_n_heads=cfg.index_n_heads, index_head_dim=cfg.index_head_dim,
        index_topk=cfg.index_topk, index_norm_eps=cfg.index_norm_eps,
    )


@pytest.fixture(scope="module")
def tiny():
    cfg = dv.DeepseekV32Config.tiny()
    params = dv.init_params(jax.random.key(0), cfg)
    # a LayerNorm that does something: the drawn weight is one and the bias zero
    layers = [
        {**p, "wi_knorm": 1.0 + 0.1 * jax.random.normal(jax.random.key(i), p["wi_knorm"].shape),
         "wi_kbias": 0.1 * jax.random.normal(jax.random.key(100 + i), p["wi_kbias"].shape)}
        for i, p in enumerate(params["layers"])
    ]
    return cfg, {**params, "layers": layers}


def sets_of(selected):
    """[..., k] positions (-1: none) -> a frozenset a row."""
    flat = np.asarray(selected).reshape(-1, selected.shape[-1])
    return [frozenset(int(v) for v in row if v >= 0) for row in flat]


def sets_of_mask(kept):
    flat = np.asarray(kept).reshape(-1, kept.shape[-1])
    return [frozenset(np.flatnonzero(row).tolist()) for row in flat]


# -- the indexer ------------------------------------------------------------------


def test_the_indexers_rotation_turns_halves_and_leaves_the_rest():
    """Pairs ``(i, i + d / 2)`` of the first ``d`` values, nothing behind
    them, where MLA's shared key turns ``(2i, 2i + 1)``; and a score between
    two rotated vectors depends on the distance of their positions alone."""
    from ray_tpu.models import latent_moe

    cfg = dv.DeepseekV32Config.tiny()
    x = jax.random.normal(jax.random.key(0), (5, 16))
    cos, sin = latent_moe.rope_tables(cfg.rope_freqs, jnp.arange(5))
    got = dv.rotate_halves(x, cos, sin)
    np.testing.assert_array_equal(got[:, 8:], x[:, 8:])
    np.testing.assert_allclose(got[:, 0], x[:, 0] * cos[:, 0] - x[:, 4] * sin[:, 0], rtol=1e-6)
    np.testing.assert_allclose(got[:, 4], x[:, 0] * sin[:, 0] + x[:, 4] * cos[:, 0], rtol=1e-6)
    # the interleaved rotation of the same values is another vector
    assert not np.allclose(got[:, :8], latent_moe.rotate(x[:, :8], cos, sin), atol=1e-3)
    a, b = x[0], x[1]
    at = lambda v, t: dv.rotate_halves(v[None], *latent_moe.rope_tables(cfg.rope_freqs, jnp.asarray([t])))[0]  # noqa: E731
    np.testing.assert_allclose(at(a, 3) @ at(b, 1), at(a, 13) @ at(b, 11), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ref.rotate_halves(x, jnp.arange(5.0)[:, None] * cfg.rope_freqs), got, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("k", [1, 5, 16, 40])
def test_the_threshold_keeps_what_a_sort_keeps(k):
    """``kept_mask`` against ``lax.top_k`` row by row, on scores with exact
    ties among them (every value three times), negative values, zeros of
    both signs, and rows that see fewer columns than ``k``: a tie goes to the
    lower column."""
    rng = np.random.default_rng(k)
    T, S = 24, 40
    scores = rng.normal(size=(T, S)).astype(np.float32).round(1)  # few values: many ties
    scores[3, :] = 0.0
    scores[4, ::2] = -0.0
    scores[5, :] = -2.5
    seen = np.arange(S)[None, :] <= (np.arange(T)[:, None] * 2)
    masked = jnp.where(seen, jnp.asarray(scores), -jnp.inf)
    got = jax.jit(functools.partial(dv.kept_mask, k=k))(masked, jnp.asarray(seen))
    vals, idx = jax.lax.top_k(masked, k)
    want = [frozenset(int(i) for v, i in zip(vs, ix) if v > -np.inf) for vs, ix in zip(np.asarray(vals), np.asarray(idx))]
    assert sets_of_mask(got) == want
    assert [len(s) for s in want] == [min(k, 2 * t + 1) for t in range(T)]


def test_a_slot_with_fewer_rows_than_places_keeps_them_all_and_says_which(tiny):
    cfg, params = tiny
    qi = jax.random.normal(jax.random.key(1), (2, cfg.index_n_heads, cfg.index_head_dim))
    w = jax.random.normal(jax.random.key(2), (2, cfg.index_n_heads))
    ikv = jax.random.normal(jax.random.key(3), (1, 9, 16, cfg.index_head_dim))
    tables = jnp.asarray([[3, 1, 4, 0], [2, 5, 7, 8]], jnp.int32)
    idx, kept = dv.select_decode(qi, w, ikv, 0, tables, jnp.asarray([5, 50]), 16)
    assert kept.sum(-1).tolist() == [5, 16]
    assert sorted(np.asarray(idx[0])[np.asarray(kept[0])].tolist()) == [0, 1, 2, 3, 4]
    assert (np.asarray(idx[1]) < 50).all() and len(set(np.asarray(idx[1]).tolist())) == 16


# -- the programs -------------------------------------------------------------------


def test_pool_is_latent_rows_and_index_keys_under_one_table(tiny):
    cfg, _ = tiny
    pool = paged.init_block_pool(cfg, 9, 16, 6)
    assert set(pool) == {"ckv", "ikv"}
    assert pool["ckv"].shape == (cfg.n_layer, 9, 16, 128) and pool["ikv"].shape == (cfg.n_layer, 9, 16, 16)
    full = paged.init_block_pool(dataclasses.replace(dv.DeepseekV32Config(), n_layer=1), 3, 16)
    assert full["ckv"].shape[-1] == 640 and full["ikv"].shape[-1] == 128
    record = paged.cache(cfg)
    assert record == paged.Cache(per_head=False, selects_rows=True)
    assert record.shares_prefixes  # nothing by slot: a pooled prefix carries both parts by block id
    assert not paged.decode_attends_in_place(cfg, 16)
    with pytest.raises(ValueError, match="kv_hooks"):
        paged.paged_verify(None, jnp.zeros((1, 2), jnp.int32), None, None, pool, cfg, block_size=16)


def test_paged_prefill_in_chunks_and_decode_are_the_reference_forward(tiny):
    """A prompt of 150 in chunks of 64 (the third chunk's tail is padding)
    and one of 23 in one bucket, scattered tables, three decode steps with a
    free slot beside them; selection keeps 16 of up to 153 positions. Logits
    against the reference's full forward, both pool parts as they lie, and
    the kept positions of every chunk's queries and every step, as sets."""
    cfg, params = tiny
    c = ref_config(cfg)
    bs, W, B, K = 16, 16, 4, 3
    rng = np.random.default_rng(0)
    lens, slots = [150, 23], [2, 0]
    toks = [rng.integers(0, cfg.vocab_size, size=n + K).astype(np.int32) for n in lens]
    fwd = jax.jit(functools.partial(ref.forward, c=c, inner=True))
    wants = [fwd(params, jnp.asarray(t)) for t in toks]
    prefill = jax.jit(functools.partial(dv.paged_prefill, cfg=cfg, block_size=bs, with_selection=True))
    decode = jax.jit(functools.partial(dv.paged_decode, cfg=cfg, block_size=bs, with_selection=True))
    pool = paged.init_block_pool(cfg, 40, bs, B)
    pool = {k: v + 3.0 for k, v in pool.items()}  # whatever lay in the blocks before must not matter
    free = list(rng.permutation(np.arange(1, 40)))
    tables = np.zeros((B, W), np.int32)
    for i, n in enumerate(lens):
        need = -(-(n + K) // bs)
        tables[slots[i], :need] = [free.pop() for _ in range(need)]
        want, inner = wants[i]
        want_sets = sets_of(inner["selected"])
        for start in range(0, n, 64):
            m = min(64, n - start)
            t = np.zeros((1, 64 if n > 32 else 32), np.int32)
            t[0, :m] = toks[i][start : start + m]
            pool, logits, counts, kept = prefill(
                params, jnp.asarray(t), jnp.asarray(m), jnp.asarray(start), jnp.asarray(tables[slots[i]]), pool,
            )
            assert counts.shape == (cfg.n_moe_layers + 1, 2) and counts[-1].tolist() == [start, m]
            assert counts[:-1, 0].tolist() == [m * cfg.experts_per_token] * cfg.n_moe_layers
            S = inner["selected"].shape[1]
            for l in range(cfg.n_layer):
                got = sets_of_mask(kept[l, :m])
                assert got == want_sets[l * S + start : l * S + start + m], (i, start, l)
        np.testing.assert_allclose(logits, want[n - 1], rtol=2e-4, atol=2e-6)
    live = np.zeros(B, bool)
    live[slots] = True
    for k in range(K):
        last, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
        for i, n in enumerate(lens):
            last[slots[i]], pos[slots[i]] = toks[i][n + k], n + k
        pool, logits, counts, (idx, kept) = decode(
            params, jnp.asarray(last), jnp.asarray(pos), jnp.asarray(tables), pool, live=jnp.asarray(live),
        )
        assert counts.shape == (cfg.n_moe_layers, 2)
        for i, n in enumerate(lens):
            want, inner = wants[i]
            np.testing.assert_allclose(logits[slots[i]], want[n + k], rtol=2e-4, atol=2e-6)
            got = sets_of(jnp.where(kept, idx, -1)[:, slots[i]])
            assert got == sets_of(inner["selected"][:, n + k]), (i, k)
    for i, n in enumerate(lens):
        _, inner = wants[i]
        for part, name, width in (("ckv", "latents", cfg.latent_dim), ("ikv", "index_keys", cfg.index_head_dim)):
            rows = np.asarray(pool[part][:, tables[slots[i]]]).reshape(cfg.n_layer, W * bs, -1)
            np.testing.assert_allclose(rows[:, : n + K, :width], inner[name], rtol=2e-4, atol=2e-6)
    # the mechanism is seen: the reference that attends everything gives other logits
    dense = jax.jit(functools.partial(ref.forward, c=c, variant="dense"))(params, jnp.asarray(toks[0]))
    assert np.abs(np.asarray(dense[lens[0] - 1] - wants[0][0][lens[0] - 1])).max() > 1e-3
    recent = jax.jit(functools.partial(ref.forward, c=c, variant="recent"))(params, jnp.asarray(toks[0]))
    assert np.abs(np.asarray(recent[lens[0] - 1] - wants[0][0][lens[0] - 1])).max() > 1e-3
    np.testing.assert_allclose(dense[:16], wants[0][0][:16], rtol=2e-4, atol=2e-6)  # up to index_topk positions: the same


def test_with_room_for_every_position_the_layer_is_mla_moes(tiny):
    """``index_topk >= max_seq``: nothing is left out, and the same weights
    give ``mla_moe``'s logits and latent rows, prefill and decode (that
    family reads no key of the indexer; the zero selection bias it honours
    changes no pick)."""
    cfg, params = tiny
    wide = dataclasses.replace(cfg, index_topk=cfg.max_seq)
    plain = mla_moe.MlaMoeConfig(**{f.name: getattr(cfg, f.name) for f in dataclasses.fields(mla_moe.MlaMoeConfig)})
    bs, W = 16, 8
    toks = np.random.default_rng(1).integers(0, cfg.vocab_size, size=(1, 64)).astype(np.int32)
    table = jnp.asarray([5, 2, 7, 3, 1, 4, 6, 8], jnp.int32)
    z = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
    pool, logits, _ = dv.paged_prefill(params, jnp.asarray(toks), z(50), z(0), table, dv.init_pool(wide, 9, bs), wide, block_size=bs)
    pool_m, logits_m, _ = mla_moe.paged_prefill(
        params, jnp.asarray(toks), z(50), z(0), table, mla_moe.init_pool(plain, 9, bs), plain, block_size=bs
    )
    np.testing.assert_allclose(logits, logits_m, rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(pool["ckv"], pool_m["ckv"], rtol=2e-4, atol=2e-6)
    step = (jnp.asarray([7, 9]), jnp.asarray([50, 3]), jnp.stack([table, table]))
    _, logits, _ = dv.paged_decode(params, *step, pool, wide, block_size=bs)
    _, logits_m, _ = mla_moe.paged_decode(params, *step, pool_m, plain, block_size=bs)
    np.testing.assert_allclose(logits[0], logits_m[0], rtol=2e-4, atol=2e-6)
    # and with 16 places the 51st position's logits are another model's
    _, narrow, _ = dv.paged_decode(params, *step, pool, cfg, block_size=bs)
    assert np.abs(np.asarray(narrow[0] - logits_m[0])).max() > 1e-3


def test_init_draws_the_indexer_and_a_zero_bias_and_counts_what_the_config_says():
    """The parameters of the published configuration by shape alone: ISSUE
    56's arithmetic for a layer (MLA 187.1 M, indexer 14.0 M), bf16 but the
    float32 router and its bias."""
    cfg = dataclasses.replace(dv.DeepseekV32Config(), n_layer=2, first_k_dense=1, experts_held=8, vocab_size=16160)
    shapes = jax.eval_shape(lambda k: dv.init_params(k, cfg), jax.random.key(0))
    size = lambda t: sum(x.size for x in jax.tree.leaves(t))  # noqa: E731
    dense, moe = shapes["layers"]
    mla = 7168 * 1536 + 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 + 512 * 128 * 256 + 16384 * 7168
    indexer = 1536 * 64 * 128 + 7168 * 128 + 7168 * 64 + 2 * 128
    assert (mla, indexer) == (187_107_328, 13_959_424)
    assert size(dense) == mla + indexer + 2 * 7168 + 3 * 7168 * 18432
    assert size(moe) == mla + indexer + 2 * 7168 + 7168 * 256 + 256 + (8 + 1) * 3 * 7168 * 2048
    assert moe["router_bias"].shape == (256,) and moe["router_bias"].dtype == jnp.float32
    assert {x.dtype for k, v in moe.items() if not k.startswith("router") for x in jax.tree.leaves(v)} == {jnp.dtype("bfloat16")}
    assert size(shapes["wte"]) == size(shapes["lm_head"]) == 16160 * 7168


def test_span_fields_count_the_selection_by_hand(tiny):
    cfg, _ = tiny
    moe = np.asarray([[20, 3], [18, 4]])
    prefill = dv.span_fields(cfg, np.concatenate([moe.reshape(-1), [64, 20], [0, 0]]), 20, 1)
    assert prefill["index_pairs_scored"] == sum(range(65, 85))
    assert prefill["latent_rows_selected"] == 20 * 16
    assert "latent_rows_read" not in prefill and prefill["picks_here"] == 38
    first = dv.span_fields(cfg, np.concatenate([moe.reshape(-1), [0, 20]]), 20, 1)
    assert first["index_pairs_scored"] == 210 and first["latent_rows_selected"] == sum(range(1, 17)) + 4 * 16
    step = dv.span_fields(cfg, moe.reshape(-1), 3, 3, decode=(np.asarray([9, 99, 15]), 12345))
    assert step["index_rows_scored"] == step["latent_rows_live"] == 10 + 100 + 16
    assert step["latent_rows_selected"] == 10 + 16 + 16
    assert step["latent_rows_read"] == 3 * 16  # the places the gather fills, not the table the kernel's arms would read


def test_a_prefill_longer_than_a_run_of_queries_selects_and_attends_run_after_run(tiny, monkeypatch):
    """``SELECT_QUERIES`` bounds the scores held at once; a bucket of several
    runs gives the logits, the rows and the kept positions of one run."""
    cfg, params = tiny
    bs = 16
    toks = np.random.default_rng(5).integers(0, cfg.vocab_size, size=(1, 128)).astype(np.int32)
    table = jnp.asarray([5, 2, 7, 3, 1, 4, 6, 8], jnp.int32)
    z = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
    run = lambda: dv.paged_prefill(  # noqa: E731
        params, jnp.asarray(toks), z(117), z(0), table, dv.init_pool(cfg, 9, bs), cfg, block_size=bs,
        with_selection=True,
    )
    pool, logits, _, kept = run()
    monkeypatch.setattr(dv, "SELECT_QUERIES", 32)
    pool4, logits4, _, kept4 = run()
    np.testing.assert_allclose(logits4, logits, rtol=2e-4, atol=2e-6)
    np.testing.assert_array_equal(kept4[:, :117], kept[:, :117])
    for part in pool:
        np.testing.assert_allclose(pool4[part], pool[part], rtol=2e-4, atol=2e-6)


def test_the_kernel_arm_of_a_chunks_attention_is_the_fold(tiny):
    """``attend_selected`` through ``ops/selected_attention.py`` in the Pallas
    interpreter against the XLA fold: a later chunk's 64 queries over 150
    positions of a scattered table, each keeping 16."""
    cfg, params = tiny
    bs = 16
    toks = np.random.default_rng(8).integers(0, cfg.vocab_size, size=(1, 192)).astype(np.int32)
    table = jnp.asarray([5, 2, 7, 3, 1, 4, 6, 8, 9, 10, 11, 12], jnp.int32)
    z = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
    pool = dv.init_pool(cfg, 13, bs)
    pool, *_ = dv.paged_prefill(params, jnp.asarray(toks[:, :128]), z(128), z(0), table, pool, cfg, block_size=bs)
    run = lambda interpret: dv.paged_prefill(  # noqa: E731
        params, jnp.asarray(toks[:, 128:]), z(50), z(128), table, pool, cfg, block_size=bs,
        with_selection=True, interpret=interpret,
    )
    (pool_f, logits_f, _, kept_f), (pool_k, logits_k, _, kept_k) = run(False), run(True)
    np.testing.assert_allclose(logits_k, logits_f, rtol=2e-4, atol=2e-6)
    np.testing.assert_array_equal(kept_k[:, :50], kept_f[:, :50])
    np.testing.assert_allclose(pool_k["ckv"], pool_f["ckv"], rtol=2e-4, atol=2e-6)


def test_one_stretch_through_the_kernel_is_one_step_of_a_running_softmax():
    """``selected_attention.attend`` alone, in the interpreter: two stretches
    walked by one call are the softmax over both under the mask, rows that
    keep nothing of the first stretch included; a third stretch of the table,
    not live, is not read (its blocks hold NaN)."""
    from ray_tpu.ops import selected_attention as sa

    H, T, S, dn, dr, Dv, R, bs = 4, 16, 32, 16, 8, 16, 32, 16
    keys = jax.random.split(jax.random.key(0), 5)
    q = jax.random.normal(keys[0], (T, H, dn + dr))
    wkvb = jax.random.normal(keys[1], (R, H * (dn + Dv))) * 0.2
    pool = jax.random.normal(keys[2], (2, 7, bs, 128)).at[:, 5:].set(jnp.nan)
    table = jnp.asarray([3, 1, 4, 2, 5, 6], jnp.int32)
    keep = jax.random.bernoulli(keys[3], 0.3, (T, 3 * S)).at[:, 2 * S - 1].set(True).at[:4, :S].set(False)
    got = sa.attend(
        q, wkvb, pool, jnp.asarray(1), table, keep, jnp.asarray(2), scale=0.3, nope=dn, pages=S // bs, interpret=True
    )
    rows = pool[1, table[:4]].reshape(2 * S, -1)
    kv = (rows[:, :R] @ wkvb).reshape(2 * S, H, dn + Dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(rows[:, None, R : R + dr], (2 * S, H, dr))], axis=-1)
    s = jnp.where(keep[None, :, : 2 * S], jnp.einsum("thd,shd->hts", q, k) * 0.3, -jnp.inf)
    want = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), kv[..., dn:])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    assert sa.fits(128, 2048, 512, 128, jnp.bfloat16) and not sa.fits(2, 64, 512, 16, jnp.float32)


@pytest.mark.parametrize(
    "start, length, case",
    [
        (0, 32, "selected"),  # one live stretch of the table's four
        (32, 32, "selected"),  # a start in the middle: two live
        (96, 32, "selected"),  # the table's last stretch
        (64, 20, "selected"),  # a ragged last chunk: rows behind the length are padding
        (64, 32, "empty"),  # rows whose selection leaves a whole stretch empty, the first among them
        (64, 32, "causal"),  # index_topk >= the context: the mask is the causal one
    ],
    ids=["start-0", "middle", "last-stretch", "ragged", "empty-stretch", "causal"],
)
def test_the_kernel_walks_the_live_stretches_as_the_fold_does(tiny, start, length, case):
    """``_kernel_selected`` (one call that walks the stretches itself, in the
    interpreter) against ``_fold_selected`` (XLA's loop) on the same
    operands: 32 queries over a scattered table of four stretches of 32
    positions; blocks behind the live stretches hold NaN, which neither reads."""
    cfg, params = tiny
    bs, T, nb = 16, 32, 2
    keys = jax.random.split(jax.random.key(start + length), 4)
    table = jnp.asarray([5, 2, 7, 3, 1, 4, 6, 8], jnp.int32)
    S = table.shape[0] * bs
    pos = start + jnp.arange(T, dtype=jnp.int32)
    n_keys = jnp.asarray(start + length, jnp.int32)
    live = (min(start + length - 1, start + T - 1) // (nb * bs) + 1) * nb  # blocks
    ckv = jax.random.normal(keys[0], (cfg.n_layer, 9, bs, cfg.pool_row_dim)).at[:, table[live:]].set(jnp.nan)
    q = jax.random.normal(keys[1], (T, cfg.n_head, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))
    seen = jnp.arange(S)[None, :] <= pos[:, None]
    if case == "causal":
        keep = seen
    else:
        scores = jax.random.normal(keys[2], (T, S))
        if case == "empty":  # nothing of the first stretch for four rows, nothing of the second for four more
            scores = scores.at[:4, :32].set(-10.0).at[4:8, 32:64].set(-10.0)
        keep = dv.kept_mask(jnp.where(seen, scores, -jnp.inf), seen, cfg.index_topk)
        if case == "empty":
            assert not keep[:4, :32].any() and not keep[4:8, 32:64].any() and keep[:8].any(axis=1).all()
    args = (q, ckv, jnp.asarray(1), table, pos, n_keys, keep, params["layers"][1]["wkvb"])
    static = dict(cfg=cfg, block_size=bs)
    want = dv._fold_selected(*args, nb=1, **static)
    got = dv._kernel_selected(*args, nb=nb, interpret=True, **static)
    assert np.isfinite(np.asarray(want[:length])).all()
    np.testing.assert_allclose(got[:length], want[:length], rtol=2e-4, atol=2e-6)
