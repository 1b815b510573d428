"""Nemotron-H's blocks on the CPU at a tiny size, against the plain reference
(benchmarks/reference/nemotron_h_ref.py, which imports nothing of the
program): each mixer alone, prefill then decode through the pool against the
full forward (logits, states, tails, keys and values), a later chunk
continuing from its slot's state, the gated norm taken by group, and the
chips' shares of an expert layer adding up to the uncut layer.
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

from benchmarks.reference import nemotron_h_ref as ref  # noqa: E402
from ray_tpu.models import latent_moe, nemotron_h as nh, paged  # noqa: E402

MM = lambda a, w: a @ w  # noqa: E731
SAME = lambda a: a  # noqa: E731


def ref_config(cfg: nh.NemotronHConfig) -> dict:
    """The reference's dictionary of published keys for ``cfg``."""
    return dict(
        hidden_size=cfg.d_model, vocab_size=cfg.vocab_size, num_hidden_layers=cfg.n_layer,
        hybrid_override_pattern=cfg.held, mamba_num_heads=cfg.mamba_heads,
        mamba_head_dim=cfg.mamba_head_dim, n_groups=cfg.ssm_groups, ssm_state_size=cfg.ssm_state,
        conv_kernel=cfg.conv_kernel, num_attention_heads=cfg.n_head,
        num_key_value_heads=cfg.n_kv_head, head_dim=cfg.head_dim,
        n_routed_experts=cfg.experts_held, expert_offset=cfg.expert_offset,
        num_experts_per_tok=cfg.experts_per_token, norm_topk_prob=cfg.renormalize,
        routed_scaling_factor=cfg.routed_scaling, layer_norm_epsilon=cfg.rms_eps,
        published=dict(n_routed_experts=cfg.n_experts),
    )


@pytest.fixture(scope="module")
def tiny():
    cfg = nh.NemotronHConfig.tiny()
    return cfg, nh.init_params(jax.random.key(0), cfg)


def _block(params, cfg, kind):
    return params["layers"][cfg.held.index(kind)]


def _zero_state(cfg):
    return (jnp.zeros((cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state)),
            jnp.zeros((cfg.conv_kernel - 1, cfg.conv_dim)))


# -- the mixers, each alone -------------------------------------------------------


@pytest.mark.parametrize("S", [7, 128, 150])
def test_mamba_prefill_is_the_token_by_token_reference(tiny, S):
    cfg, params = tiny
    p = _block(params, cfg, "M")
    u = jax.random.normal(jax.random.key(1), (S, cfg.d_model))
    out, h, tail = nh.mamba_prefill(u, p, cfg, *_zero_state(cfg), jnp.asarray(S))
    want, state, rows = ref.mamba(u[None], p, ref_config(cfg), MM, jnp.asarray([S]))
    np.testing.assert_allclose(out, want[0], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(h, state[0], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(tail, rows[0], rtol=2e-4, atol=2e-6)


def test_mamba_decode_steps_on_from_a_prefill(tiny):
    cfg, params = tiny
    p = _block(params, cfg, "M")
    S, K = 40, 3
    u = jax.random.normal(jax.random.key(2), (S + K, cfg.d_model))
    want, state, rows = ref.mamba(u[None], p, ref_config(cfg), MM, jnp.asarray([S + K]))
    _, h, tail = nh.mamba_prefill(u[:S], p, cfg, *_zero_state(cfg), jnp.asarray(S))
    for k in range(K):
        out, h, tail = nh.mamba_decode(u[S + k][None], p, cfg, h[None], tail[None])
        h, tail = h[0], tail[0]
        np.testing.assert_allclose(out[0], want[0, S + k], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(h, state[0], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(tail, rows[0], rtol=2e-4, atol=2e-6)


def test_mamba_padded_tail_and_continuation(tiny):
    """A bucket of 64 with 37 real rows leaves the state and tail of 37; the
    next run, from that state and tail, gives what one run of the whole
    gives."""
    cfg, params = tiny
    p = _block(params, cfg, "M")
    u = jax.random.normal(jax.random.key(3), (64, cfg.d_model))
    whole, h_whole, tail_whole = nh.mamba_prefill(u, p, cfg, *_zero_state(cfg), jnp.asarray(64))
    first, h, tail = nh.mamba_prefill(u, p, cfg, *_zero_state(cfg), jnp.asarray(37))
    cut, h_cut, tail_cut = nh.mamba_prefill(u[:37], p, cfg, *_zero_state(cfg), jnp.asarray(37))
    np.testing.assert_allclose(h, h_cut, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(tail, tail_cut, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(first[:37], cut, rtol=1e-5, atol=1e-6)
    rest, h2, tail2 = nh.mamba_prefill(u[37:], p, cfg, h, tail, jnp.asarray(27))
    np.testing.assert_allclose(rest, whole[37:], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(h2, h_whole, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(tail2, tail_whole, rtol=1e-4, atol=1e-6)


def test_the_gated_norm_is_per_group(tiny):
    """Scaling one group's channels of ``y`` leaves that group's output as it
    was (its own RMS divides the scale out) and every other group's untouched;
    a norm over all channels would move them all. And the reference's control
    that takes it over all channels differs from the reference."""
    cfg, params = tiny
    y = jax.random.normal(jax.random.key(4), (5, cfg.d_inner))
    z = jax.random.normal(jax.random.key(5), (5, cfg.d_inner))
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.key(6), (cfg.d_inner,))
    g = cfg.d_inner // cfg.ssm_groups
    out = nh.gated_norm(y, z, scale, cfg)
    out_scaled = nh.gated_norm(y.at[:, :g].multiply(7.0), z, scale, cfg)
    np.testing.assert_allclose(out_scaled, out, rtol=1e-4, atol=1e-6)
    want = (y * jax.nn.silu(z)).reshape(5, cfg.ssm_groups, g)
    want = want / jnp.sqrt(jnp.mean(want**2, axis=-1, keepdims=True) + cfg.rms_eps)
    np.testing.assert_allclose(out, want.reshape(5, -1) * scale, rtol=1e-5, atol=1e-6)
    p = _block(params, cfg, "M")
    u = jax.random.normal(jax.random.key(7), (1, 20, cfg.d_model))
    at = jnp.asarray([20])
    right, _, _ = ref.mamba(u, p, ref_config(cfg), MM, at)
    wrong, _, _ = ref.mamba(u, p, ref_config(cfg), MM, at, wrong="ungrouped_norm")
    assert float(jnp.linalg.norm(wrong - right) / jnp.linalg.norm(right)) > 0.01


def test_attention_prefill_and_decode_are_the_reference(tiny):
    """20 positions (and the padding behind them to two whole blocks, as a
    prefill program's rows are) written under a scattered table and attended,
    then one more by the decode form (the gather, and the kernel in the interpreter):
    the reference's causal attention, which knows no position."""
    cfg, params = tiny
    p = _block(params, cfg, "*")
    bs, S = 16, 20
    u = jax.random.normal(jax.random.key(8), (S + 1, cfg.d_model))
    want, kv = ref.attention(u[None], p, ref_config(cfg), MM, SAME)
    pool = nh.init_pool(cfg, 6, bs, 2)
    pk, pv = pool["k"] + 3.0, pool["v"] - 2.0  # whatever lay there before must not matter
    table = jnp.asarray([4, 2, 0, 0], jnp.int32)
    padded = jnp.concatenate([u[:S], jnp.ones((2 * bs - S, cfg.d_model))])
    out, pk, pv = nh.attention_prefill(padded, p, cfg, pk, pv, 0, table, jnp.arange(2 * bs), bs)
    np.testing.assert_allclose(out[:S], want[0, :S], rtol=2e-4, atol=2e-6)
    tables = jnp.stack([table, jnp.zeros(4, jnp.int32)])
    positions = jnp.asarray([S, 0])
    for interpret in (False, True):
        attend = paged.decode_attention(paged.attention_kind(cfg), bs, None, interpret)
        o, k1, v1 = nh.attention_decode(
            jnp.stack([u[S], u[0]]), p, cfg, pk, pv, 0, tables, positions, bs, attend
        )
        np.testing.assert_allclose(o[0], want[0, S], rtol=2e-4, atol=2e-6)
    rows = lambda a: np.asarray(a[0, table[:2]]).transpose(0, 2, 1, 3).reshape(2 * bs, -1)[: S + 1]  # noqa: E731
    np.testing.assert_allclose(
        np.concatenate([rows(k1), rows(v1)], axis=-1), kv[0], rtol=2e-4, atol=2e-6
    )


@pytest.mark.parametrize("arm", ["ragged_dot", "kernel"])
def test_the_expert_layer_is_the_reference_in_its_latent(tiny, monkeypatch, arm):
    """Ungated and in its latent, whichever arm computes the grouped products
    (the kernel in the interpreter: tests/moe_ffn_golden.py:take_arm)."""
    import moe_ffn_golden as golden

    cfg, params = tiny
    p = _block(params, cfg, "E")
    assert "e_gate" not in p and "s_gate" not in p and p["latent_in"].shape == (cfg.d_model, cfg.moe_latent)
    u = jax.random.normal(jax.random.key(9), (40, cfg.d_model))
    plain = latent_moe.moe_ffn(u, p, cfg)
    golden.take_arm(monkeypatch, arm)
    y, counts, picks = latent_moe.moe_ffn(u, p, cfg)
    assert np.array_equal(picks, plain[2]) and np.array_equal(counts, plain[1])
    np.testing.assert_allclose(y, plain[0], rtol=1e-4, atol=1e-7)  # the stored cases' tolerance
    want, idx = ref.experts(u, p, ref_config(cfg), MM)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-6)
    assert (np.sort(picks, -1) == np.sort(idx, -1)).all()
    assert int(counts[0]) == 40 * cfg.experts_per_token
    unsquared, _ = ref.experts(u, p, ref_config(cfg), MM, wrong="unsquared")
    assert float(jnp.linalg.norm(unsquared - want) / jnp.linalg.norm(want)) > 0.1


def test_tokens_marked_invalid_touch_no_expert(tiny):
    cfg, params = tiny
    p = _block(params, cfg, "E")
    u = jax.random.normal(jax.random.key(10), (12, cfg.d_model))
    _, counts, _ = latent_moe.moe_ffn(u, p, cfg, jnp.arange(12) < 5)
    assert int(counts[0]) == 5 * cfg.experts_per_token
    _, none, _ = latent_moe.moe_ffn(u, p, cfg, jnp.zeros(12, bool))
    assert none.tolist() == [0, 0]


@pytest.mark.parametrize("arm", ["ragged_dot", "kernel"])
@pytest.mark.parametrize("rows", [8, 24, 64])
def test_the_grouped_products_in_passes_give_what_one_pass_gives(tiny, monkeypatch, rows, arm):
    """40 tokens x 3 picks = 120 sorted rows in the latent, a chip holding
    experts 2-4: in passes of 8, 24 or 64 rows, through either arm of the
    grouped products, against all in one pass of ``ragged_dot`` and against
    the reference's masked loop."""
    import moe_ffn_golden as golden

    cfg, params = tiny
    p = _block(params, cfg, "E")
    share = dataclasses.replace(cfg, experts_held=3, expert_offset=2)
    held = {k: v[2:5] if k.startswith("e_") else v for k, v in p.items()}
    u = jax.random.normal(jax.random.key(11), (40, cfg.d_model))
    valid = jnp.arange(40) < 37
    whole, counts, picks = latent_moe.moe_ffn(u, held, share, valid)
    golden.take_arm(monkeypatch, arm)
    monkeypatch.setattr(latent_moe, "ROWS_A_PASS", rows)
    y, c, i = jax.jit(lambda u, held, valid: latent_moe.moe_ffn(u, held, share, valid))(u, held, valid)
    assert 0 < int(counts[0]) < 111 and np.array_equal(c, counts) and np.array_equal(i, picks)
    np.testing.assert_allclose(y, whole, rtol=1e-4, atol=2e-6)
    one, _ = ref.experts(u[:37], held, ref_config(share), MM)
    np.testing.assert_allclose(y[:37], one, rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("skew", [False, True])
def test_the_four_shares_add_up_to_the_uncut_layer(tiny, skew):
    """Four chips hold two of the eight experts each. The routed parts they
    give, each through ``W_2`` (which every chip holds whole: the sum of the
    parts through it is the whole through it), summed, with the shared expert
    (which every chip computes alike) and the residual counted once, are what
    the uncut reference's expert block gives."""
    cfg, params = tiny
    p = _block(params, cfg, "E")
    if skew:  # every token's best expert is 6: the chip that holds it does most of the work
        p = {**p, "router": p["router"].at[:, 6].set(p["router"][:, 6] * 0 + 0.5)}
    x = jax.random.normal(jax.random.key(12), (40, cfg.d_model))
    u = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + cfg.rms_eps)
    y_whole, _ = ref.experts(u, p, ref_config(cfg), MM)
    whole = x + y_whole  # the uncut block, with its residual
    shared = jnp.square(jax.nn.relu(u @ p["s_up"])) @ p["s_down"]
    routed, here = 0.0, 0
    for chip in range(4):
        share = dataclasses.replace(cfg, experts_held=2, expert_offset=2 * chip)
        held = {k: v[2 * chip : 2 * chip + 2] if k.startswith("e_") else v for k, v in p.items()}
        y, counts, _ = latent_moe.moe_ffn(u, held, share)
        routed += y - shared
        here += int(counts[0])
        one, _ = ref.experts(u, held, ref_config(share), MM)
        np.testing.assert_allclose(y, one, rtol=2e-4, atol=2e-6)  # a share alone, too
    assert here == 40 * cfg.experts_per_token  # every pick landed on exactly one chip
    np.testing.assert_allclose(x + routed + shared, whole, rtol=2e-4, atol=5e-6)


def test_balancing_the_selection_bias_evens_the_experts_load():
    """The rule this family shares with Kimi Linear, over this family's
    prefill: a tilted selection bias is evened out, and nothing but the bias
    moves; init_params leaves a bias that is not zero."""
    cfg = nh.NemotronHConfig.tiny(pattern="ME*E")
    params = nh.draw_params(jax.random.key(2), cfg)
    at = [n for n, p in enumerate(params["layers"]) if "router_bias" in p]
    assert len(at) == 2 and all(not params["layers"][n]["router_bias"].any() for n in at)
    # at this size the experts are nearly even by themselves: tilt them as
    # the common part of the hidden states tilts them at the published widths
    tilt = jnp.linspace(-0.3, 0.3, cfg.n_experts)
    params["layers"] = [
        {**p, "router_bias": tilt} if "router_bias" in p else p for p in params["layers"]
    ]
    balanced = jax.jit(
        lambda ps, key: latent_moe.balance_routers(ps, key, cfg, 64, 128, nh.init_pool, nh.paged_prefill)
    )(params, jax.random.key(3))
    toks = jax.random.randint(jax.random.key(4), (1, 128), 0, cfg.vocab_size)

    def loads(ps):
        *_, picks = nh.paged_prefill(
            ps, toks, jnp.asarray(128), jnp.asarray(0), jnp.arange(1, 9), nh.init_pool(cfg, 9, 16, 0),
            cfg, block_size=16, with_picks=True,
        )
        return np.stack([np.bincount(np.asarray(l).reshape(-1), minlength=cfg.n_experts) for l in picks])

    before, after = loads(params), loads(balanced)
    assert before.sum() == after.sum() == cfg.n_moe_layers * 128 * cfg.experts_per_token
    assert (after.std(axis=1) < 0.5 * before.std(axis=1)).all(), (before, after)
    p, b = params["layers"][at[0]], balanced["layers"][at[0]]
    assert all(np.array_equal(p[k], b[k]) for k in p if k != "router_bias")
    drawn = nh.init_params(jax.random.key(2), cfg)
    assert all(drawn["layers"][n]["router_bias"].any() for n in at)


def test_the_experts_down_projections_sum_to_zero_over_their_hidden_units():
    """What ``draw_params`` does about the squared ReLU's positive mean (its
    ``down`` says why): every output's weights sum to zero over the hidden
    units, for the routed experts and the shared one, so that the part of the
    activation that is alike at every unit reaches no token. A gateless expert
    fed one constant at every hidden unit gives nothing."""
    cfg = nh.NemotronHConfig.tiny(pattern="E")
    p = nh.draw_params(jax.random.key(5), cfg)["layers"][0]
    for name in ("e_down", "s_down"):
        sums = jnp.sum(p[name], axis=-2)
        assert float(jnp.abs(sums).max()) < 1e-6 * p[name].shape[-2], name
        assert float(jnp.std(p[name])) > 0.5 * 0.02 / cfg.n_layer**0.5  # still a random matrix
    const = jnp.full((3, cfg.shared_d_ff), 0.7)
    np.testing.assert_allclose(const @ p["s_down"], 0.0, atol=1e-6)
    # in bfloat16, as served (centred in float32, then cast), rounding leaves a hundredth of a plain draw's sums
    served = dataclasses.replace(cfg, param_dtype=jnp.bfloat16, moe_d_ff=2688)
    e_down = nh.draw_params(jax.random.key(5), served)["layers"][0]["e_down"].astype(jnp.float32)
    plain = 0.02 * 2688**0.5  # the sum of 2688 draws of N(0, 0.02)
    assert float(jnp.sqrt(jnp.mean(jnp.sum(e_down, axis=-2) ** 2))) < 0.01 * plain


def _plain_down(params, key, cfg):
    """``params`` with every expert's down projection redrawn N(0, s) as it
    comes, uncentred: the draw that ``draw_params`` gave up."""
    layers = []
    for n, p in enumerate(params["layers"]):
        if "e_down" in p:
            k1, k2 = jax.random.split(jax.random.fold_in(key, n))
            p = {**p, "e_down": 0.02 * jax.random.normal(k1, p["e_down"].shape),
                 "s_down": 0.02 / cfg.n_layer**0.5 * jax.random.normal(k2, p["s_down"].shape)}
        layers.append(p)
    return {**params, "layers": layers}


def _logits_in_common(params, cfg, slots=8, prompt=12, steps=6):
    """``slots`` random prompts prefilled, then decoded greedily through the
    pool: the mean cosine between two slots' logits, over the pairs and the
    steps. Unlike contexts that share nothing read about 0."""
    bs, W = 16, 2
    rng = np.random.default_rng(0)
    pool = nh.init_pool(cfg, 1 + slots * W, bs, slots)
    tables = jnp.arange(1, 1 + slots * W, dtype=jnp.int32).reshape(slots, W)
    prefill = jax.jit(functools.partial(nh.paged_prefill, cfg=cfg, block_size=bs))
    decode = jax.jit(functools.partial(nh.paged_decode, cfg=cfg, block_size=bs))
    last = []
    for b in range(slots):
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 16)), jnp.int32)
        pool, logits, _ = prefill(
            params, toks, jnp.int32(prompt), jnp.int32(0), tables[b], pool, slot=jnp.int32(b))
        last.append(int(jnp.argmax(logits)))
    last, pos = jnp.asarray(last, jnp.int32), jnp.full((slots,), prompt, jnp.int32)
    cosines = []
    for _ in range(steps):
        pool, logits, _ = decode(params, last, pos, tables, pool)
        unit = np.asarray(logits) / np.linalg.norm(logits, axis=1, keepdims=True)
        cosines.append((unit @ unit.T)[~np.eye(slots, dtype=bool)].mean())
        last, pos = jnp.argmax(logits, -1).astype(jnp.int32), pos + 1
    return float(np.mean(cosines))


@pytest.mark.parametrize("seed", [0, 1])
def test_centred_down_projections_keep_the_contexts_apart(seed):
    """The effect that the zero sums are for (``draw_params``' ``down``), held
    through the blocks: eight unlike prompts, prefilled and decoded greedily
    through the pool, have logits with nothing in common under the centred
    draw, and a common part under the plain one (every expert's down
    projection N(0, s) as it comes, all else the same). The model's width is
    the published 4096, so that N(0, 0.02) gives the experts' hidden units the
    scale they have when served (at ``.tiny()``'s 64 their output is a
    thousandth of the residual stream and neither draw moves a logit); every
    other size is tiny. What this size shows is the common vector, at cosines
    of 0.2-0.3; the slots' 0.80 and the one token that won every context were
    read at the served sizes on the chip (PERF.md section 6, PR 35)."""
    cfg = nh.NemotronHConfig.tiny(d_model=4096, moe_latent=64, moe_d_ff=256, shared_d_ff=512)
    centred = nh.draw_params(jax.random.key(seed), cfg)
    plain = _plain_down(centred, jax.random.key(100 + seed), cfg)
    apart, alike = _logits_in_common(centred, cfg), _logits_in_common(plain, cfg)
    assert abs(apart) < 0.08, apart  # read: 0.01-0.03
    assert alike > 0.15 and alike > 4 * abs(apart), (alike, apart)  # read: 0.23-0.28


# -- the paged programs -----------------------------------------------------------


def test_pool_is_blocks_of_keys_and_values_and_a_state_per_slot(tiny):
    cfg, _ = tiny
    pool = paged.init_block_pool(cfg, 9, 16, 5)
    n_m, n_a = cfg.held.count("M"), cfg.held.count("*")
    assert pool["k"].shape == pool["v"].shape == (n_a, 9, cfg.n_kv_head, 16, cfg.head_dim)
    assert pool["state"].shape == (n_m, 6, cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state)
    assert pool["state"].dtype == jnp.float32
    assert pool["conv"].shape == (n_m, 6, cfg.conv_kernel - 1, cfg.conv_dim)
    assert paged.init_block_pool(cfg, 9, 16)["state"].shape[1] == cfg.state_slots + 1
    assert paged.cache(cfg) == paged.Cache(slot_state=True, per_head=True, hooks=False)
    assert not paged.decode_attends_in_place(cfg, 16)  # no TPU here: the gather
    with pytest.raises(ValueError, match="recurrent state"):
        paged.paged_verify(None, jnp.zeros((1, 2), jnp.int32), None, None, pool, cfg, block_size=16)


@pytest.mark.parametrize("interpret", [False, True], ids=["gather", "kernel_interpreted"])
def test_paged_prefill_and_decode_are_the_reference_forward(tiny, interpret):
    """Two prompts in two buckets and scattered tables, three decode steps
    with a free (not live) slot beside them: logits against the reference's
    full forward position by position, then the states, the tails and the
    keys and values as they lie in the pool."""
    cfg, params = tiny
    c = ref_config(cfg)
    bs, W, B, K = 16, 8, 4, 3
    rng = np.random.default_rng(0)
    lens, slots = [50, 23], [2, 0]
    toks = rng.integers(0, cfg.vocab_size, size=(2, max(lens) + K)).astype(np.int32)
    want, inner = ref.forward(
        params, jnp.asarray(toks), c, inner=True, keep_at=[n + K for n in lens]
    )
    prefill = jax.jit(functools.partial(paged.paged_prefill, cfg=cfg, block_size=bs))
    decode = jax.jit(functools.partial(nh.paged_decode, cfg=cfg, block_size=bs, interpret=interpret))
    pool = paged.init_block_pool(cfg, 20, bs, B)
    pool = {k: v + 3.0 for k, v in pool.items()}  # whatever lay there before must not matter
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
            slot=jnp.asarray(slots[i]),
        )
        np.testing.assert_allclose(logits, want[i, n - 1], rtol=2e-4, atol=2e-6)
        assert counts.shape == (cfg.n_moe_layers, 2)
        assert counts[:, 0].tolist() == [n * cfg.experts_per_token] * cfg.n_moe_layers
    live = np.zeros(B, bool)
    live[slots] = True
    bystander = np.asarray(pool["state"][:, 1])  # slot 1 is free: nothing may step it
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
    np.testing.assert_array_equal(pool["state"][:, 1], bystander)
    for i, n in enumerate(lens):
        s = slots[i]
        np.testing.assert_allclose(pool["state"][:, s], inner["state"][:, i], rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(pool["conv"][:, s], inner["conv"][:, i], rtol=2e-4, atol=2e-6)
        rows = lambda a: np.asarray(a[:, tables[s]]).transpose(0, 1, 3, 2, 4).reshape(a.shape[0], W * bs, -1)  # noqa: E731
        kv = np.concatenate([rows(pool["k"]), rows(pool["v"])], axis=-1)
        np.testing.assert_allclose(kv[:, : n + K], inner["kv"][:, i, : n + K], rtol=2e-4, atol=2e-6)
    _, _, _, picks = nh.paged_prefill(
        params, jnp.asarray(toks[:1, :32]), jnp.asarray(32), jnp.asarray(0),
        jnp.asarray(tables[2]), paged.init_block_pool(cfg, 20, bs), cfg, block_size=bs, with_picks=True,
    )
    assert (np.sort(picks, -1) == np.sort(inner["picks"][:, 0, :32], -1)).all()


def test_a_later_chunk_continues_from_its_slots_state_and_a_fresh_one_ignores_it(tiny):
    """Positions 0-31 in one bucket, then 32-52 with ``start`` 32 into the same
    slot: the last logits, state, tail and rows of one prefill of 53. A prefill
    from position 0 into a slot that holds another sequence's state gives what
    it gives into a zeroed one."""
    cfg, params = tiny
    bs = 16
    toks = np.random.default_rng(3).integers(0, cfg.vocab_size, size=(1, 64)).astype(np.int32)
    table = jnp.asarray([5, 2, 7, 3], jnp.int32)
    prefill = jax.jit(functools.partial(paged.paged_prefill, cfg=cfg, block_size=bs))
    z = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
    fresh = lambda: paged.init_block_pool(cfg, 9, bs, 3)  # noqa: E731
    one_pool, one, _ = prefill(params, jnp.asarray(toks), z(53), z(0), table, fresh(), slot=z(1))
    pool, _, _ = prefill(params, jnp.asarray(toks[:, :32]), z(32), z(0), table, fresh(), slot=z(1))
    second = np.zeros((1, 32), np.int32)
    second[0, :21] = toks[0, 32:53]
    pool, two, counts = prefill(params, jnp.asarray(second), z(21), z(32), table, pool, slot=z(1))
    np.testing.assert_allclose(two, one, rtol=2e-4, atol=2e-6)
    assert counts[:, 0].tolist() == [21 * cfg.experts_per_token] * cfg.n_moe_layers
    for part in ("state", "conv"):
        np.testing.assert_allclose(pool[part][:, 1], one_pool[part][:, 1], rtol=2e-4, atol=2e-5)
    rows = lambda p: np.asarray(p["k"][:, table]).transpose(0, 1, 3, 2, 4).reshape(-1, 64, cfg.n_kv_head * cfg.head_dim)[:, :53]  # noqa: E731
    np.testing.assert_allclose(rows(pool), rows(one_pool), rtol=2e-4, atol=2e-6)
    # a slot that another sequence left: start == 0 begins from zero all the same
    used_pool, used, _ = prefill(params, jnp.asarray(toks), z(53), z(0), table, pool, slot=z(1))
    np.testing.assert_allclose(used, one, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(used_pool["state"][:, 1], one_pool["state"][:, 1], rtol=1e-5, atol=1e-6)


def test_init_draws_in_the_parameter_dtype_and_counts_what_the_config_says():
    """The parameters of the published configuration by shape alone (nothing is
    drawn): bf16 everywhere but the float32 routers and the state-space
    scalars, and the sizes of ISSUE 35's arithmetic for each kind of block."""
    cfg = dataclasses.replace(nh.NemotronHConfig(), n_layer=11, experts_held=128, vocab_size=32768)
    assert cfg.held == "MEMEMEM*EME"
    shapes = jax.eval_shape(lambda k: nh.init_params(k, cfg), jax.random.key(0))
    size = lambda t: sum(x.size for x in jax.tree.leaves(t))  # noqa: E731
    by_kind = {k: shapes["layers"][cfg.held.index(k)] for k in "ME*"}
    mamba = 4096 * (8192 + 10240 + 128) + 5 * 10240 + 3 * 128 + 8192 + 8192 * 4096 + 4096
    assert size(by_kind["M"]) == mamba == 109_640_064
    assert size(by_kind["*"]) == 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096 == 35_655_680
    outside = 4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096
    assert outside == 54_530_560
    assert size(by_kind["E"]) == outside + 128 * 2 * 1024 * 2688
    assert size(shapes["wte"]) == size(shapes["lm_head"]) == 32768 * 4096
    total = size(shapes)
    assert total == 5 * mamba + 35_655_680 + 5 * (outside + 128 * 5_505_024) + 2 * 32768 * 4096 + 4096
    assert round(total / 1e6) == 4648
    e = by_kind["E"]
    assert e["router"].dtype == e["router_bias"].dtype == jnp.float32
    assert {x.dtype for k, v in e.items() if not k.startswith("router") for x in jax.tree.leaves(v)} == {jnp.dtype("bfloat16")}
    m = by_kind["M"]
    assert {m[k].dtype for k in ("dt_bias", "A_log", "D")} == {jnp.dtype("float32")}
    assert m["w_in"].dtype == m["conv_w"].dtype == m["conv_b"].dtype == jnp.bfloat16
    # the uncut model by the same shapes: the published 120 B
    whole = 40 * mamba + 8 * 35_655_680 + 40 * (outside + 512 * 5_505_024) + 2 * 131072 * 4096 + 4096
    assert round(whole / 1e9, 1) == 120.7
