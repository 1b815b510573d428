"""The ``mla_moe`` family (latent attention with a rotated shared key and a
low-rank query in every layer, group-limited routing) on the CPU at a tiny
size in float32, against the plain reference
(benchmarks/reference/mla_moe_ref.py, which imports nothing of the program),
and what it shares with Kimi Linear (ray_tpu/models/latent_moe.py) held to
what Kimi Linear's own code gave.

Tolerances: float32 on both sides, so 2e-4 relative (1e-4 or tighter where one
matrix product separates the two forms) with an absolute floor of a few 1e-6
for values that cancel: the two sides sum in different orders (absorbed against
expanded, a running softmax against a whole one, sorted groups against a
masked loop), nothing else.
"""

import dataclasses
import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import mla_moe_ref as ref  # noqa: E402
from ray_tpu.models import kimi_linear as kl, latent_moe, mla_moe as mm, paged  # noqa: E402


def ref_config(cfg: mm.MlaMoeConfig) -> dict:
    """The reference's dictionary of published keys for ``cfg``."""
    return dict(
        hidden_size=cfg.d_model, vocab_size=cfg.vocab_size, num_hidden_layers=cfg.n_layer,
        num_attention_heads=cfg.n_head, q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        intermediate_size=cfg.d_ff, moe_intermediate_size=cfg.moe_d_ff,
        n_routed_experts=cfg.experts_held, expert_offset=cfg.expert_offset,
        num_experts_per_tok=cfg.experts_per_token, n_shared_experts=cfg.n_shared_experts,
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        first_k_dense_replace=cfg.first_k_dense, norm_topk_prob=cfg.renormalize,
        routed_scaling_factor=cfg.routed_scaling, rms_norm_eps=cfg.rms_eps,
        rope_theta=cfg.rope_theta,
        rope_scaling=dict(
            type="yarn", factor=cfg.rope_factor, beta_fast=cfg.rope_beta_fast,
            beta_slow=cfg.rope_beta_slow, mscale=cfg.rope_mscale,
            mscale_all_dim=cfg.rope_mscale_all_dim,
            original_max_position_embeddings=cfg.rope_original_max,
        ),
        published=dict(n_routed_experts=cfg.n_experts),
    )


@pytest.fixture(scope="module")
def tiny():
    cfg = mm.MlaMoeConfig.tiny()
    return cfg, mm.init_params(jax.random.key(0), cfg)


# -- rotation -------------------------------------------------------------------


def test_yarn_frequencies_and_scale_at_the_published_keys_are_the_closed_form():
    """A.X-K1's ``rope_scaling``: correction range 10 and 23, frequencies
    divided by 32 below it, left alone above it, a ramp between; cos and sin
    unscaled; the softmax scale times (0.1 ln 32 + 1)^2."""
    cfg = mm.MlaMoeConfig()
    turns = lambda beta: 64 * math.log(4096 / (beta * 2 * math.pi)) / (2 * math.log(10000))  # noqa: E731
    assert (math.floor(turns(32)), math.ceil(turns(1))) == (10, 23)
    i = np.arange(32)
    plain = 10000.0 ** (-2 * i / 64)
    m = 1 - np.clip((i - 10) / 13, 0, 1)
    want = (1 - m) * plain / 32 + m * plain
    np.testing.assert_allclose(cfg.rope_freqs, want, rtol=1e-6)
    np.testing.assert_allclose(cfg.rope_freqs[:11], plain[:11], rtol=1e-6)  # fast pairs: as they were
    np.testing.assert_allclose(cfg.rope_freqs[23:], plain[23:] / 32, rtol=1e-6)  # slow pairs: stretched
    ym = 0.1 * math.log(32) + 1
    assert ym == pytest.approx(1.3466, abs=1e-4) and cfg.rope_mscale_ratio == 1.0
    assert cfg.softmax_scale == pytest.approx(192**-0.5 * ym * ym)
    assert cfg.softmax_scale / 192**-0.5 == pytest.approx(1.813, abs=1e-3)
    # the reference reads the same from the published keys, by its own code
    freqs, mscale, scale = ref.frequencies(ref_config(cfg))
    np.testing.assert_allclose(freqs, want, rtol=1e-6)
    assert mscale == 1.0 and scale == pytest.approx(cfg.softmax_scale)
    # no stretch: plain frequencies and the plain scale
    plain_cfg = dataclasses.replace(cfg, rope_factor=1.0)
    np.testing.assert_allclose(plain_cfg.rope_freqs, plain, rtol=1e-6)
    assert plain_cfg.softmax_scale == pytest.approx(192**-0.5)


def test_rotation_turns_interleaved_pairs_and_scores_depend_on_distance_only():
    freqs = latent_moe.rope_frequencies(8, 10000.0)
    x = jax.random.normal(jax.random.key(0), (5, 8))
    pos = jnp.asarray([0, 1, 7, 30, 31])
    turned = latent_moe.rotate(x, *latent_moe.rope_tables(freqs, pos))
    np.testing.assert_allclose(turned[0], x[0], rtol=1e-6)  # position 0: no turn
    for t in range(5):
        for i in range(4):
            a = float(pos[t]) * float(freqs[i])
            want = np.array([[math.cos(a), -math.sin(a)], [math.sin(a), math.cos(a)]]) @ np.asarray(x[t, 2 * i : 2 * i + 2])
            np.testing.assert_allclose(turned[t, 2 * i : 2 * i + 2], want, rtol=1e-4, atol=1e-5)
    q, k = x[1], x[2]
    at = lambda v, t: latent_moe.rotate(v[None], *latent_moe.rope_tables(freqs, jnp.asarray([t])))[0]  # noqa: E731
    assert float(at(q, 9) @ at(k, 4)) == pytest.approx(float(at(q, 25) @ at(k, 20)), rel=1e-4)
    assert float(at(q, 9) @ at(k, 4)) != pytest.approx(float(at(q, 9) @ at(k, 5)), rel=1e-3)


# -- latent attention -----------------------------------------------------------


def _pool_of(rows, bs=16):
    """A one-layer latent pool holding ``rows`` [S, C] under the table 1..n."""
    S, C = rows.shape
    n = S // bs
    ckv = jnp.zeros((1, n + 1, bs, C), rows.dtype).at[0, 1:].set(rows.reshape(n, bs, C))
    return ckv, jnp.arange(1, n + 1, dtype=jnp.int32)


def _expanded(h, rows, pos, p, cfg, rope, scale):
    """Attention over latent rows with every key and value expanded and the
    whole score matrix at once: what ``mla_prefill`` must give."""
    H, dn, dv, R = cfg.n_head, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    q = latent_moe.mla_query(h, p, cfg, rope)
    kv = (rows[:, :R] @ p["wkvb"]).reshape(-1, H, dn + dv)
    s = jnp.einsum("thd,shd->hts", q[..., :dn], kv[..., :dn])
    s = (s + jnp.einsum("thd,sd->hts", q[..., dn:], rows[:, R:])) * scale
    s = jnp.where(jnp.arange(rows.shape[0])[None, :] <= pos[:, None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), kv[..., dn:])
    return o.reshape(h.shape[0], H * dv) @ p["wo"]


@pytest.mark.parametrize("key_positions", [16, 32, 512])
def test_prefill_by_stretches_of_the_table_is_the_whole_expanded_attention(tiny, key_positions):
    """Four, two and one stretches of a 64-position table, a chunk that starts
    at position 16 and ends inside the third block: the running softmax gives
    what one softmax over the whole row gives, and reads nothing behind the
    newest row."""
    cfg, params = tiny
    p = params["layers"][1]
    S, start, T, n = 64, 16, 32, 23  # 23 real tokens in a bucket of 32
    h_all = jax.random.normal(jax.random.key(1), (S, cfg.d_model))
    pos_all = jnp.arange(S)
    rope_all = latent_moe.rope_tables(cfg.rope_freqs, pos_all)
    rows = latent_moe.mla_latent(h_all, p, cfg, rope_all)
    # Rows behind the chunk are another request's or padding: masked where a
    # stretch holds them beside visible rows, and not read at all where it
    # holds none (a stretch of one block: NaN there would show).
    rows = rows.at[start + T :].set(jnp.nan if key_positions == 16 else 50.0)
    ckv, table = _pool_of(rows)
    pos = start + jnp.arange(T)
    rope = latent_moe.rope_tables(cfg.rope_freqs, pos)
    got = latent_moe.mla_prefill(
        h_all[start : start + T], ckv, 0, table, pos, jnp.asarray(start + n), p, cfg,
        block_size=16, rope=rope, scale=cfg.softmax_scale, key_positions=key_positions,
    )
    want = _expanded(h_all[start : start + T], rows[: start + T], pos, p, cfg, rope, cfg.softmax_scale)
    assert np.isfinite(np.asarray(got[:n])).all()
    np.testing.assert_allclose(got[:n], want[:n], rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("interpret", [False, True], ids=["gather", "kernel_interpreted"])
def test_absorbed_decode_is_the_expanding_form(tiny, interpret):
    """By either arm of ``paged.latent_decode_attention``: over the gathered
    table, and over the live blocks with the kernel in the interpreter."""
    cfg, params = tiny
    p = params["layers"][0]
    S, n = 32, 21
    h = jax.random.normal(jax.random.key(2), (S, cfg.d_model))
    pos = jnp.arange(S)
    rope = latent_moe.rope_tables(cfg.rope_freqs, pos)
    rows = latent_moe.mla_latent(h, p, cfg, rope)
    expanded = _expanded(h, rows, pos, p, cfg, rope, cfg.softmax_scale)
    # the rows as a one-layer pool under the table 2, 1
    ckv = jnp.zeros((1, 3, 16, rows.shape[-1])).at[0, jnp.asarray([2, 1])].set(rows.reshape(2, 16, -1))
    attend = paged.latent_decode_attention(cfg, 16, None, interpret, cfg.softmax_scale)

    def decode(rope_at):
        one = tuple(a[rope_at][None] for a in rope)
        return latent_moe.mla_decode(
            h[n][None], ckv, 0, jnp.asarray([[2, 1]]), jnp.asarray([n + 1]), p, cfg, attend, one
        )

    absorbed = decode(n)
    np.testing.assert_allclose(absorbed[0], expanded[n], rtol=1e-4, atol=2e-6)
    # the rotation matters: the same query as if it stood elsewhere reads another mixture
    elsewhere = decode(3)
    assert float(jnp.abs(elsewhere - absorbed).max()) > 1e-3 * float(jnp.abs(absorbed).max())


def test_the_query_goes_through_its_low_rank_pair_and_norm(tiny):
    cfg, params = tiny
    p = params["layers"][0]
    assert p["wq_a"].shape == (cfg.d_model, 24) and "wq" not in p
    h = jax.random.normal(jax.random.key(3), (4, cfg.d_model))
    c_q = h @ p["wq_a"]
    c_q = c_q / jnp.sqrt(jnp.mean(c_q * c_q, -1, keepdims=True) + cfg.rms_eps) * p["q_norm"]
    want = (c_q @ p["wq_b"]).reshape(4, cfg.n_head, -1)
    np.testing.assert_allclose(latent_moe.mla_query(h, p, cfg), want, rtol=1e-5, atol=1e-6)
    # Kimi Linear's layers have the one matrix, and the same function takes it
    kcfg = kl.KimiLinearConfig.tiny()
    kp = kl.init_params(jax.random.key(0), kcfg)["layers"][3]
    assert "wq" in kp and "wq_a" not in kp
    np.testing.assert_allclose(
        latent_moe.mla_query(h, kp, kcfg), (h @ kp["wq"]).reshape(4, kcfg.n_head, -1), rtol=1e-6
    )


# -- routing ----------------------------------------------------------------------


def test_grouped_selection_never_leaves_its_groups():
    """Eight experts a token of 24 in 6 groups, held to 2 groups: every pick
    lies in one of the token's two best groups by the sum of their two largest
    scores, also where better experts sit in a third group."""
    cfg = mm.MlaMoeConfig.tiny(n_experts=24, experts_held=24, n_group=6, topk_group=2, experts_per_token=8)
    router = jax.random.normal(jax.random.key(5), (cfg.d_model, 24)) * cfg.d_model**-0.5
    h = jax.random.normal(jax.random.key(6), (200, cfg.d_model))
    idx, w = latent_moe.route(h, {"router": router}, cfg)
    s = np.asarray(jax.nn.sigmoid(h @ router))
    top2 = np.sort(s.reshape(200, 6, 4), axis=-1)[..., -2:].sum(-1)
    best = np.argsort(-top2, axis=-1)[:, :2]
    groups = np.asarray(idx) // 4
    assert all(set(g) == set(b) for g, b in zip(groups, best))  # 8 picks fill both groups of 4
    left_out = sum(  # the plain top-8 would have left the groups for many tokens
        set(np.argsort(-row)[:8] // 4) != set(b) for row, b in zip(s, best)
    )
    assert left_out > 100
    np.testing.assert_allclose(w.sum(-1), cfg.routed_scaling, rtol=1e-5)
    # the reference chooses the same, by its own code
    ridx, rw = ref.route(h, {"router": router}, ref_config(cfg), lambda a, b: a @ b)
    assert (np.sort(idx, -1) == np.sort(ridx, -1)).all()
    np.testing.assert_allclose(np.sort(w, -1), np.sort(rw, -1), rtol=1e-5)


def test_one_group_and_a_bias_is_exactly_kimi_linears_router():
    """What ``kimi_linear.route`` computed before the two families shared it,
    written out here: chosen by score plus bias, weighted by score."""
    cfg = kl.KimiLinearConfig.tiny()
    assert (cfg.n_group, cfg.topk_group) == (1, 1)
    p = {
        "router": jax.random.normal(jax.random.key(7), (cfg.d_model, 8)) * 0.2,
        "router_bias": jnp.linspace(-0.2, 0.2, 8),
    }
    h = jax.random.normal(jax.random.key(8), (50, cfg.d_model))
    idx, w = kl.route(h, p, cfg)
    assert kl.route is latent_moe.route
    s = jax.nn.sigmoid(jnp.dot(h, p["router"], precision=jax.lax.Precision.HIGHEST))
    _, want_idx = jax.lax.top_k(s + p["router_bias"], cfg.experts_per_token)
    want_w = jnp.take_along_axis(s, want_idx, axis=-1)
    want_w = want_w / want_w.sum(-1, keepdims=True) * cfg.routed_scaling
    assert np.array_equal(idx, want_idx) and np.array_equal(w, want_w)
    # one group of the grouped code is the plain code, bit for bit, with or without the bias
    grouped = mm.MlaMoeConfig.tiny(n_group=1, topk_group=1, routed_scaling=cfg.routed_scaling)
    for q in (p, {"router": p["router"]}):
        a, b = latent_moe.route(h, q, grouped), latent_moe.route(h, q, cfg)
        assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


@pytest.mark.parametrize("arm", ["ragged_dot", "kernel"])
def test_the_expert_layer_gives_what_it_gave_before_it_learnt_a_latent(monkeypatch, arm):
    """As in test_kimi_linear.py, for this family's grouped selection without
    a bias: a layer with gates and no latent pair gets from ``moe_ffn`` the
    numbers it gave before (tests/moe_ffn_golden.py), whichever arm computes
    the grouped products."""
    import moe_ffn_golden as golden

    golden.take_arm(monkeypatch, arm)
    h, p, share, valid = golden.case(mm.MlaMoeConfig.tiny(), offset=2, held=4, bias=False)
    golden.assert_as_before("mla_moe", latent_moe.moe_ffn(h, p, share, valid))


@pytest.mark.parametrize("family", ["mla_moe", "deepseek_v32"])
@pytest.mark.parametrize("skew", [False, True])
def test_the_four_shares_add_up_to_the_uncut_layer(tiny, skew, family):
    """Four chips hold two of the eight experts each (a chip's share is one
    group here; the router still ranks all four groups). Their routed parts,
    with the shared expert (which every chip computes alike) counted once,
    are what the uncut reference layer gives. ``deepseek_v32``: the same
    layer drawn by that family, with a selection bias (``noaux_tc``: it moves
    the choice and not the weights) that every share carries whole."""
    cfg, params = tiny
    if family == "deepseek_v32":
        from ray_tpu.models import deepseek_v32

        cfg = deepseek_v32.DeepseekV32Config.tiny()
        params = deepseek_v32.init_params(jax.random.key(0), cfg)
    p = params["layers"][2]
    if "router_bias" in p:
        p = {**p, "router_bias": 0.3 * jax.random.normal(jax.random.key(9), p["router_bias"].shape)}
    if skew:  # every token's best expert is 6: the chip that holds it does most of the work
        p = {**p, "router": p["router"].at[:, 6].set(p["router"][:, 6] * 0 + 0.5)}
    h = jax.random.normal(jax.random.key(6), (40, cfg.d_model))
    whole, _ = ref.moe(h, p, ref_config(cfg), lambda a, w: a @ w)
    shared = (jax.nn.silu(h @ p["s_gate"]) * (h @ p["s_up"])) @ p["s_down"]
    routed, here = 0.0, 0
    for chip in range(4):
        share = dataclasses.replace(cfg, experts_held=2, expert_offset=2 * chip)
        held = {k: v[2 * chip : 2 * chip + 2] if k.startswith("e_") else v for k, v in p.items()}
        y, counts, _ = latent_moe.moe_ffn(h, held, share)
        routed += y - shared
        here += int(counts[0])
        one, _ = ref.moe(h, held, ref_config(share), lambda a, w: a @ w)
        np.testing.assert_allclose(y, one, rtol=2e-4, atol=2e-6)  # a share alone, too
    assert here == 40 * cfg.experts_per_token  # every pick landed on exactly one chip
    np.testing.assert_allclose(routed + shared, whole, rtol=2e-4, atol=5e-6)


@pytest.mark.parametrize("rows", [8, 24, 64])
def test_the_grouped_products_in_passes_give_what_one_pass_gives(tiny, monkeypatch, rows):
    """40 tokens x 2 picks = 80 sorted rows, of which a chip holding experts
    2-4 is landed on by some: taken 8, 24 (80 is no multiple: the last pass
    is padded) or 64 rows a pass, for as many passes as hold a landed pair
    (a traced number under ``jit``), against all 80 in one pass and against
    the reference's masked loop."""
    cfg, params = tiny
    p = params["layers"][1]
    share = dataclasses.replace(cfg, experts_held=3, expert_offset=2)
    held = {k: v[2:5] if k.startswith("e_") else v for k, v in p.items()}
    h = jax.random.normal(jax.random.key(8), (40, cfg.d_model))
    valid = jnp.arange(40) < 37
    whole, counts, picks = latent_moe.moe_ffn(h, held, share, valid)
    monkeypatch.setattr(latent_moe, "ROWS_A_PASS", rows)
    y, c, i = jax.jit(lambda h, held, valid: latent_moe.moe_ffn(h, held, share, valid))(h, held, valid)
    assert 0 < int(counts[0]) < 74 and np.array_equal(c, counts) and np.array_equal(i, picks)
    np.testing.assert_allclose(y, whole, rtol=1e-4, atol=2e-6)
    one, _ = ref.moe(h[:37], held, ref_config(share), lambda a, w: a @ w)
    np.testing.assert_allclose(y[:37], one, rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("sequences", [1, 4])
def test_centred_routers_spread_the_picks_and_add_no_term(tiny, sequences):
    """A common part in every hidden state tilts an uncentred router towards
    the experts whose columns lie along it; centring (on one seeded sequence
    or on the mean over several) takes the tilt out of the weights. No bias is
    drawn, and nothing but the routers differs."""
    cfg = mm.MlaMoeConfig.tiny(n_layer=2, n_experts=16, experts_held=16, n_group=1, topk_group=1)
    drawn = mm.draw_params(jax.random.key(2), cfg)
    # at this size the experts are nearly even by themselves: tilt them, as the
    # common part of the hidden states tilts them at the published widths
    tilt = jnp.ones((cfg.d_model, 1)) * jnp.linspace(-0.05, 0.05, 16)[None, :]
    drawn["layers"][1]["router"] = drawn["layers"][1]["router"] + tilt
    drawn["wte"] = drawn["wte"] + 0.05  # a common part in every token's embedding
    centred = mm.centre_routers(drawn, jax.random.key(3), cfg, sequences, 128 // sequences)
    toks = jax.random.randint(jax.random.key(4), (1, 128), 0, cfg.vocab_size)

    def loads(ps):
        *_, picks = mm.paged_prefill(
            ps, toks, jnp.asarray(128), jnp.asarray(0), jnp.arange(1, 9), mm.init_pool(cfg, 9, 16),
            cfg, block_size=16, with_picks=True,
        )
        return np.bincount(np.asarray(picks).reshape(-1), minlength=16)

    before, after = loads(drawn), loads(centred)
    assert before.sum() == after.sum() == 128 * cfg.experts_per_token
    assert after.std() < 0.5 * before.std(), (before, after)
    a, b = drawn["layers"][1], centred["layers"][1]
    assert "router_bias" not in b
    assert all(np.array_equal(a[k], b[k]) for k in a if k != "router")
    assert all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(drawn["layers"][0]), jax.tree.leaves(centred["layers"][0])))


# -- the paged programs --------------------------------------------------------------


def test_pool_is_latent_rows_and_nothing_else(tiny):
    cfg, _ = tiny
    pool = paged.init_block_pool(cfg, 9, 16, 6)
    # a row is the latent row and zeros up to whole 128-lane tiles (latent_moe.whole_tiles)
    assert (cfg.latent_dim, cfg.pool_row_dim, mm.MlaMoeConfig().pool_row_dim) == (40, 128, 640)
    assert set(pool) == {"ckv"} and pool["ckv"].shape == (cfg.n_layer, 9, 16, 128)
    assert paged.cache(cfg) == paged.Cache(slot_state=False, per_head=False, hooks=False)
    assert paged.cache(kl.KimiLinearConfig.tiny()) == paged.Cache(
        slot_state=True, delta_rule=True, per_head=False, hooks=False
    )
    with pytest.raises(ValueError, match="kv_hooks"):
        paged.paged_verify(None, jnp.zeros((1, 2), jnp.int32), None, None, pool, cfg, block_size=16)


def test_paged_prefill_and_decode_are_the_reference_forward(tiny):
    """Two prompts in two buckets and scattered tables, three decode steps
    with a free (not live) slot beside them: logits against the reference's
    full forward position by position, and the rows as they lie in the pool."""
    cfg, params = tiny
    c = ref_config(cfg)
    bs, W, B, K = 16, 8, 4, 3
    rng = np.random.default_rng(0)
    lens, slots = [50, 23], [2, 0]
    toks = rng.integers(0, cfg.vocab_size, size=(2, max(lens) + K)).astype(np.int32)
    want, inner = ref.forward(params, jnp.asarray(toks), c, inner=True)
    prefill = jax.jit(functools.partial(paged.paged_prefill, cfg=cfg, block_size=bs))
    decode = jax.jit(functools.partial(paged.paged_decode, cfg=cfg, block_size=bs))
    pool = paged.init_block_pool(cfg, 20, bs, B)
    pool["ckv"] = pool["ckv"] + 3.0  # whatever lay in the blocks before must not matter
    free = list(rng.permutation(np.arange(1, 20)))
    tables = np.zeros((B, W), np.int32)
    for i, n in enumerate(lens):
        need = -(-(n + K) // bs)
        tables[slots[i], :need] = [free.pop() for _ in range(need)]
        bucket = 64 if n > 32 else 32
        t = np.zeros((1, bucket), np.int32)
        t[0, :n] = toks[i, :n]
        pool, logits, counts = prefill(
            params, jnp.asarray(t), jnp.asarray(n), jnp.asarray(0), jnp.asarray(tables[slots[i]]), pool,
        )
        np.testing.assert_allclose(logits, want[i, n - 1], rtol=2e-4, atol=2e-6)
        assert counts.shape == (cfg.n_moe_layers, 2)
        assert counts[:, 0].tolist() == [n * cfg.experts_per_token] * cfg.n_moe_layers
    live = np.zeros(B, bool)
    live[slots] = True
    for k in range(K):
        last, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
        for i, n in enumerate(lens):
            last[slots[i]], pos[slots[i]] = toks[i, n + k], n + k
        pool, logits, counts = decode(
            params, jnp.asarray(last), jnp.asarray(pos), jnp.asarray(tables), pool, live=jnp.asarray(live),
        )
        for i, n in enumerate(lens):
            np.testing.assert_allclose(logits[slots[i]], want[i, n + k], rtol=2e-4, atol=2e-6)
        assert counts[:, 0].tolist() == [2 * cfg.experts_per_token] * cfg.n_moe_layers
    for i, n in enumerate(lens):
        rows = np.asarray(pool["ckv"][:, tables[slots[i]]]).reshape(cfg.n_layer, W * bs, -1)
        np.testing.assert_allclose(
            rows[:, : n + K, : cfg.latent_dim], inner["latents"][:, i, : n + K], rtol=2e-4, atol=2e-6
        )
        assert not rows[:, : n + K, cfg.latent_dim :].any()  # zeros behind the latent row
    _, _, _, picks = mm.paged_prefill(
        params, jnp.asarray(toks[:1, :32]), jnp.asarray(32), jnp.asarray(0),
        jnp.asarray(tables[2]), paged.init_block_pool(cfg, 20, bs), cfg, block_size=bs, with_picks=True,
    )
    assert (np.sort(picks, -1) == np.sort(inner["picks"][:, 0, :32], -1)).all()


def test_a_prompt_prefilled_in_two_chunks_is_one_prefill(tiny):
    """Positions 0-31 in one bucket, then 32-52 with ``start`` 32 over the
    rows the first chunk left: the same last logits and the same rows as one
    prefill of 53, and the second chunk's padded tail past position 52 is not
    attended."""
    cfg, params = tiny
    bs = 16
    toks = np.random.default_rng(3).integers(0, cfg.vocab_size, size=(1, 64)).astype(np.int32)
    table = jnp.asarray([5, 2, 7, 3], jnp.int32)
    prefill = jax.jit(functools.partial(paged.paged_prefill, cfg=cfg, block_size=bs))
    z = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
    one_pool, one, _ = prefill(params, jnp.asarray(toks), z(53), z(0), table, paged.init_block_pool(cfg, 9, bs))
    pool, _, _ = prefill(params, jnp.asarray(toks[:, :32]), z(32), z(0), table, paged.init_block_pool(cfg, 9, bs))
    second = np.zeros((1, 32), np.int32)
    second[0, :21] = toks[0, 32:53]
    pool, two, counts = prefill(params, jnp.asarray(second), z(21), z(32), table, pool)
    np.testing.assert_allclose(two, one, rtol=2e-4, atol=2e-6)
    assert counts[:, 0].tolist() == [21 * cfg.experts_per_token] * cfg.n_moe_layers
    rows = lambda p: np.asarray(p["ckv"][:, table]).reshape(cfg.n_layer, 64, -1)[:, :53]  # noqa: E731
    np.testing.assert_allclose(rows(pool), rows(one_pool), rtol=2e-4, atol=2e-6)


def test_init_draws_in_the_parameter_dtype_and_counts_what_the_config_says():
    """The parameters of the published configuration by shape alone (nothing is
    drawn): bf16 everywhere but the float32 routers, and the sizes of ISSUE 33's
    arithmetic for one layer."""
    cfg = dataclasses.replace(mm.MlaMoeConfig(), n_layer=2, experts_held=12, vocab_size=20480)
    shapes = jax.eval_shape(lambda k: mm.init_params(k, cfg), jax.random.key(0))
    size = lambda t: sum(x.size for x in jax.tree.leaves(t))  # noqa: E731
    dense, moe = shapes["layers"]
    mla = 7168 * 1536 + 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 + 512 * 64 * 256 + 8192 * 7168
    assert mla == 101_124_096  # 11.01 + 18.87 + 4.13 + 8.39 + 58.72 M and the two norms
    assert size(dense) == mla + 2 * 7168 + 3 * 7168 * 18432
    assert size(moe) == mla + 2 * 7168 + 7168 * 192 + (12 + 1) * 3 * 7168 * 2048
    assert "router_bias" not in moe and moe["router"].dtype == jnp.float32
    assert {x.dtype for k, v in moe.items() if k != "router" for x in jax.tree.leaves(v)} == {jnp.dtype("bfloat16")}
    assert size(shapes["wte"]) == size(shapes["lm_head"]) == 20480 * 7168
