"""Kimi Linear's layers on the CPU at a tiny size, against the plain
reference (benchmarks/reference/kimi_linear_ref.py, which imports nothing of
the program): the chunked delta rule against the one-token form and the
token-by-token recurrence, latent attention absorbed against expanded, the
expert layer without dropped tokens, and the chips' shares of an expert layer
adding up to the uncut layer.
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

from benchmarks.reference import kimi_linear_ref as ref  # noqa: E402
from ray_tpu.models import kda, kimi_linear as kl, latent_moe, paged  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.ops import delta_rule, delta_scan  # noqa: E402


def ref_config(cfg: kl.KimiLinearConfig) -> dict:
    """The reference's dictionary of published keys for ``cfg``."""
    return dict(
        hidden_size=cfg.d_model, vocab_size=cfg.vocab_size, num_hidden_layers=cfg.n_layer,
        num_attention_heads=cfg.n_head, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim, qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim, intermediate_size=cfg.d_ff,
        moe_intermediate_size=cfg.moe_d_ff, num_experts=cfg.experts_held,
        expert_offset=cfg.expert_offset, num_experts_per_token=cfg.experts_per_token,
        num_shared_experts=cfg.n_shared_experts, first_k_dense_replace=cfg.first_k_dense,
        moe_renormalize=cfg.renormalize, routed_scaling_factor=cfg.routed_scaling,
        rms_norm_eps=cfg.rms_eps, param_dtype="float32", dtype="float32",
        linear_attn_config=dict(
            num_heads=cfg.kda_heads, head_dim=cfg.kda_head_dim,
            short_conv_kernel_size=cfg.conv_kernel,
            kda_layers=list(cfg.kda_layers), full_attn_layers=list(cfg.mla_layers),
        ),
        assumed=dict(kda_gate_rank=cfg.kda_gate_rank),
        published=dict(num_experts=cfg.n_experts),
    )


# The chunked scan's two lowerings, held to the same closeness: the plain
# ``lax.scan`` (the one definition) and the kernel that keeps the state and a
# chunk's values on the chip, here in the Pallas interpreter.
ARMS = {
    "plain": delta_rule.kda_chunked,
    "kernel_interpreted": functools.partial(delta_scan.kda_scan, interpret=True),
}
arms = pytest.mark.parametrize("arm", list(ARMS))


def _kda_inputs(key, T, H=3, d=8, decay=(0.001, 1.6), beta_max=1.0):
    ks = jax.random.split(key, 6)
    l2 = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = l2(jax.random.normal(ks[0], (T, H, d))) * d**-0.5
    k = l2(jax.random.normal(ks[1], (T, H, d)))
    v = jax.random.normal(ks[2], (T, H, d))
    # log decays from -0.001 to -1.6 a token, as the model's initialiser
    # gives them; the strong ones are past what exp(-sum) could be divided by.
    g = -jax.random.uniform(ks[3], (T, H, d), minval=decay[0], maxval=decay[1])
    beta = beta_max * jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    S0 = jax.random.normal(ks[5], (H, d, d))
    return q, k, v, g, beta, S0


@pytest.mark.parametrize("beta_max", [1.0, 2.0], ids=["beta_under_1", "beta_under_2"])
@pytest.mark.parametrize(
    "T, H, d",
    # 15-17 and 79 end inside a sub-block of delta_rule.BLOCK rows and on its
    # edges; the last two are the two cells' heads cut down at their width.
    [(T, 3, 8) for T in (1, 15, 16, 17, 63, 64, 79, 130)] + [(70, 2, 128), (40, 4, 128)],
)
@arms
def test_kda_chunked_is_the_step_is_the_recurrence(T, H, d, beta_max, arm):
    """``beta`` in (0, 1), Kimi Linear's, and in (0, 2), Solar Open 2's: past
    1 the transition has a negative eigenvalue along ``k``."""
    q, k, v, g, beta, S0 = _kda_inputs(jax.random.key(T), T, H, d, beta_max=beta_max)
    assert float(beta.max()) < beta_max and (T == 1 or float(beta.max()) > beta_max / 2)
    o_c, S_c = ARMS[arm](q, k, v, g, beta, S0)
    S, o_s = S0, []
    for t in range(T):
        o, S = delta_rule.kda_step(q[t], k[t], v[t], g[t], beta[t], S)
        o_s.append(o)
    o_r, S_r = ref.kda_recurrence(*(a[None] for a in (q, k, v, g, beta, S0)))
    np.testing.assert_allclose(o_c, jnp.stack(o_s), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(S_c, S, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(o_c, o_r[0], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(S_c, S_r[0], rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize(
    "over, decay, beta_max",
    [("chunk", (2.0, 6.0), 1.0), ("chunk", (2.0, 6.0), 2.0), ("sub_block", (6.0, 9.0), 2.0)],
)
@arms
def test_kda_chunked_survives_decays_no_product_could_be_divided_by(over, decay, beta_max, arm):
    """exp(-sum of g) over a chunk, or over one sub-block of it, is far past
    float32 here: the chunked form must never form it. Past a sub-block the
    factor ``exp(G_r - G_i)`` of an earlier column underflows while a row's
    ``exp(G_t - G_r)`` does not: the term it loses is one the recurrence
    lost too."""
    q, k, v, g, beta, S0 = _kda_inputs(jax.random.key(7), 100, decay=decay, beta_max=beta_max)
    span = {"chunk": delta_rule.CHUNK, "sub_block": delta_rule.BLOCK}[over]
    assert float(jnp.sum(g[:span], axis=0).max()) < -89  # exp(89) is past float32
    if over == "sub_block":
        G = jnp.cumsum(g[: delta_rule.CHUNK], axis=0)
        assert float(jnp.exp(G[delta_rule.BLOCK] - G[0]).max()) == 0.0
        assert float(jnp.exp(G[delta_rule.BLOCK + 1] - G[delta_rule.BLOCK]).min()) > 0.0
    o_c, S_c = ARMS[arm](q, k, v, g, beta, S0)
    o_r, S_r = ref.kda_recurrence(*(a[None] for a in (q, k, v, g, beta, S0)))
    assert np.isfinite(np.asarray(o_c)).all() and np.isfinite(np.asarray(S_c)).all()
    np.testing.assert_allclose(o_c, o_r[0], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(S_c, S_r[0], rtol=2e-4, atol=2e-5)


def _walk(jaxpr):
    """Every equation of a traced program, those of its loops' bodies too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _kda_chunked_jaxpr(T, H, d):
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    return jax.make_jaxpr(delta_rule.kda_chunked)(
        spec(T, H, d), spec(T, H, d), spec(T, H, d), spec(T, H, d), spec(T, H), spec(H, d, d)
    ).jaxpr


def test_kda_chunked_never_forms_a_decay_for_every_pair_of_a_chunk():
    """The pair terms go by sub-blocks: no value of the traced program, the
    scan's body included, is as large as ``[H, CHUNK, CHUNK, d_k]``, so none
    is that for every chunk of the call either."""
    n, H, d = 4, 4, 128
    C = delta_rule.CHUNK
    shapes = [v.aval.shape for eqn in _walk(_kda_chunked_jaxpr(n * C, H, d)) for v in eqn.outvars]
    sizes = [int(np.prod(shape)) for shape in shapes]
    assert len(sizes) > 50 and max(sizes) >= n * C * H * d  # the walk reached the scan's body
    assert max(sizes) < H * C * C * d
    assert not [shape for shape in shapes if shape[-3:] == (C, C, d)]


def test_kda_chunked_calls_no_solver():
    """The chunk's system is inverted by blocks: no ``triangular_solve`` is
    left in the traced program. One scan over the chunks carries the state,
    and the only loop inside it is the diagonal blocks' elimination."""
    n, H, d = 4, 4, 128
    jaxpr = _kda_chunked_jaxpr(n * delta_rule.CHUNK, H, d)
    names = [eqn.primitive.name for eqn in _walk(jaxpr)]
    assert not [name for name in names if "solve" in name or name == "custom_call"]
    loops = lambda jaxpr: [e for e in _walk(jaxpr) if e.primitive.name in ("scan", "while")]  # noqa: E731
    over_chunks = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(over_chunks) == 1 and over_chunks[0].params["length"] == n
    inner = loops(over_chunks[0].params["jaxpr"].jaxpr)
    assert len(inner) == 1 and len(loops(jaxpr)) == 2
    blocks = (H, delta_rule.CHUNK // delta_rule.BLOCK, delta_rule.BLOCK, delta_rule.BLOCK)
    assert [v.aval.shape for v in inner[0].outvars if v.aval.ndim] == [blocks]


@pytest.mark.parametrize("size", [0.2, 1.0, 2.0], ids=["entries_to_0.2", "entries_to_1", "entries_to_2"])
@pytest.mark.parametrize("H", [3, 64])
def test_the_inverse_by_blocks_is_the_triangular_solve(H, size):
    """``(I + A)^-1`` by blocks against ``solve_triangular(I + A, I)`` on
    random strictly lower ``A``: nothing is assumed of the entries. At 2 the
    inverse's own reach 1e10 and float32 holds neither result to more than
    three digits of the largest, so each matrix is read by its distance from
    the inverse taken in float64, over its largest entry: the blocks' may be
    a few times the solver's (their merging products are not a substitution)
    and no more."""
    C = delta_rule.CHUNK
    A = jnp.tril(jax.random.uniform(jax.random.key(H), (H, C, C), minval=-size, maxval=size), -1)
    eye = jnp.eye(C)
    inv = np.asarray(delta_rule._unit_lower_inverse(A))
    solved = np.asarray(jax.scipy.linalg.solve_triangular(
        eye + A, jnp.broadcast_to(eye, A.shape), lower=True, unit_diagonal=True
    ))
    exact = np.linalg.inv(np.eye(C) + np.asarray(A, np.float64))
    far = lambda X: np.abs(X - exact).max(axis=(-2, -1)) / np.abs(exact).max(axis=(-2, -1))  # noqa: E731
    assert (far(inv) <= np.maximum(10 * far(solved), 2e-6)).all()
    assert far(inv).max() < (3e-6 if size <= 1 else 1e-3)
    assert (np.triu(inv, 1) == 0).all() and (np.diagonal(inv, axis1=-2, axis2=-1) == 1).all()
    if size < 1:  # an inverse of entries near one: every entry to its own size too
        np.testing.assert_allclose(inv, exact, rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("decay", [(0.01, 0.1), (0.001, 1.6)], ids=["slow_decay", "initialiser_decay"])
@pytest.mark.parametrize("T, H, d", [(200, 3, 8), (130, 2, 128)])
@arms
def test_kda_chunked_at_beta_2_on_keys_nearly_parallel(T, H, d, decay, arm):
    """The system's worst case here: ``beta`` 1.99 at every position and keys
    a few degrees apart, so every entry under the diagonal is near 2 (where
    the decay leaves it: a channel keeps half of itself over a chunk at the
    slow end) and the writes alternate in sign: a series in the system's
    powers would have terms past 2^15 before they cancel. (With next to no
    decay at all, 0.0001-0.01 a token, the chunked form leaves these
    tolerances whoever solves it: substitution by 3.5 times at 128 wide, the
    blocks by 5.)"""
    q, _, v, g, _, S0 = _kda_inputs(jax.random.key(T), T, H, d, decay=decay)
    ks = jax.random.split(jax.random.key(d), 2)
    k = jax.random.normal(ks[0], (1, H, d)) + 0.05 * jax.random.normal(ks[1], (T, H, d))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    assert float(jnp.einsum("thd,shd->hts", k, k).min()) > 0.7
    beta = jnp.full((T, H), 1.99)
    o_c, S_c = ARMS[arm](q, k, v, g, beta, S0)
    o_r, S_r = ref.kda_recurrence(*(a[None] for a in (q, k, v, g, beta, S0)))
    np.testing.assert_allclose(o_c, o_r[0], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(S_c, S_r[0], rtol=2e-4, atol=2e-5)


@arms
def test_kda_positions_with_beta_0_and_g_0_leave_the_state_alone(arm):
    q, k, v, g, beta, S0 = _kda_inputs(jax.random.key(3), 40)
    live = (jnp.arange(40) < 27)[:, None]
    _, S_pad = ARMS[arm](q, k, v, g * live[..., None], beta * live, S0)
    _, S_cut = ARMS[arm](q[:27], k[:27], v[:27], g[:27], beta[:27], S0)
    np.testing.assert_allclose(S_pad, S_cut, rtol=1e-6, atol=1e-7)


@pytest.fixture(scope="module")
def tiny():
    cfg = kl.KimiLinearConfig.tiny()
    return cfg, kl.init_params(jax.random.key(0), cfg)


@arms
def test_kda_prefill_padded_tail_and_continuation(tiny, arm):
    """The state and the convolution tail after a padded bucket are those at
    ``length``; a second chunk that continues from them gives what one
    prefill of the whole gives. By either arm of the scan: the kernel's takes
    the state as :func:`paged.state_prefill` hands it over, held."""
    cfg, params = tiny
    p = params["layers"][0]
    h = jax.random.normal(jax.random.key(1), (48, cfg.d_model))
    H, d = cfg.kda_heads, cfg.kda_head_dim
    held = (lambda S: S) if arm == "plain" else functools.partial(delta_scan.Held, interpret=True)
    zeros = held(jnp.zeros((H, d, d))), jnp.zeros((cfg.conv_kernel - 1, cfg.conv_dim))
    whole, S_w, tail_w = kda.kda_prefill(h[:37], p, cfg, *zeros, 37)
    padded, S_p, tail_p = kda.kda_prefill(h, p, cfg, *zeros, 37)
    np.testing.assert_allclose(S_p, S_w, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(tail_p, tail_w, rtol=1e-6)
    np.testing.assert_allclose(padded[:37], whole, rtol=1e-5, atol=1e-6)
    first, S_1, tail_1 = kda.kda_prefill(h[:16], p, cfg, *zeros, 16)
    second, S_2, tail_2 = kda.kda_prefill(h[16:37], p, cfg, held(S_1), tail_1, 21)
    np.testing.assert_allclose(jnp.concatenate([first, second]), whole, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(S_2, S_w, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(tail_2, tail_w, rtol=1e-6)
    # ... and one token at a time, as decode steps
    S, tail, outs = S_1[None], tail_1[None], []
    for t in range(16, 37):
        o, S, tail = kda.kda_decode(h[t][None], p, cfg, S, tail)
        outs.append(o[0])
    np.testing.assert_allclose(jnp.stack(outs), whole[16:], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(S[0], S_w, rtol=2e-4, atol=2e-5)


def test_mla_absorbed_decode_is_the_expanded_attention(tiny):
    cfg, params = tiny
    p = params["layers"][3]
    assert cfg.mixer(4) == "mla"
    S, n = 32, 21
    h = jax.random.normal(jax.random.key(2), (S, cfg.d_model))
    rows = kl.mla_latent(h, p, cfg)
    # the rows as a one-layer pool under the table 1, 2 (the prefill reads them there)
    ckv = jnp.zeros((1, 3, 16, rows.shape[-1])).at[0, 1:].set(rows.reshape(2, 16, -1))
    expanded = kl.mla_prefill(
        h, ckv, 0, jnp.asarray([1, 2]), jnp.arange(S), jnp.asarray(S), p, cfg, block_size=16
    )
    attend = paged.latent_decode_attention(cfg, 16, None, False, latent_moe.mla_scale(cfg))
    absorbed = kl.mla_decode(
        h[n][None], ckv, 0, jnp.asarray([[1, 2]]), jnp.asarray([n + 1]), p, cfg, attend
    )
    np.testing.assert_allclose(absorbed[0], expanded[n], rtol=2e-4, atol=2e-6)


def _skewed(p, expert: int):
    """A router whose every token's first pick is ``expert``."""
    return {**p, "router_bias": p["router_bias"].at[expert].set(100.0)}


def test_expert_layer_drops_nothing_when_every_token_picks_one_expert(tiny):
    """No capacity: all T tokens land on expert 5 and all T are computed."""
    cfg, params = tiny
    p = _skewed(params["layers"][1], 5)
    T = 64
    h = jax.random.normal(jax.random.key(4), (T, cfg.d_model))
    y, counts, picks = kl.moe_ffn(h, p, cfg)
    assert (np.asarray(picks) == 5).sum(axis=1).tolist() == [1] * T
    assert int(counts[0]) == T * cfg.experts_per_token  # all 8 experts are held
    want, _ = ref.moe(h, p, ref_config(cfg), lambda a, w: a @ w)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-6)
    # the token-dropping layer this one is not would have kept T / 8 of them
    held = {k: v[5:6] if k.startswith("e_") else v for k, v in p.items()}
    _, counts5, _ = kl.moe_ffn(
        h, held, dataclasses.replace(cfg, experts_held=1, expert_offset=5),
    )
    assert counts5.tolist() == [T, 1]


def test_tokens_marked_invalid_touch_no_expert(tiny):
    cfg, params = tiny
    p = params["layers"][1]
    h = jax.random.normal(jax.random.key(5), (16, cfg.d_model))
    valid = jnp.arange(16) < 3
    y, counts, _ = kl.moe_ffn(h, p, cfg, valid)
    y3, counts3, _ = kl.moe_ffn(h[:3], p, cfg)
    assert counts.tolist() == counts3.tolist() == [3 * cfg.experts_per_token, int(counts3[1])]
    np.testing.assert_allclose(y[:3], y3, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("arm", ["ragged_dot", "kernel"])
@pytest.mark.parametrize("rows_a_pass", [None, 24], ids=["one_pass", "in_passes"])
def test_the_expert_layer_gives_what_it_gave_before_it_learnt_a_latent(monkeypatch, rows_a_pass, arm):
    """``moe_ffn`` now also serves a family without gates and with a latent
    around the routed part; a layer of this family's holds neither, and gets
    the numbers the function gave before (tests/moe_ffn_golden.py: stored
    outputs, bit for bit when this was written), a share of the experts held,
    padding rows invalid, whole or in passes, and whichever arm computes the
    grouped products."""
    import moe_ffn_golden as golden

    golden.take_arm(monkeypatch, arm)
    if rows_a_pass:
        monkeypatch.setattr(latent_moe, "ROWS_A_PASS", rows_a_pass)
    h, p, share, valid = golden.case(kl.KimiLinearConfig.tiny(), offset=2, held=3, bias=True)
    golden.assert_as_before(
        "kimi_linear_in_passes" if rows_a_pass else "kimi_linear", kl.moe_ffn(h, p, share, valid))


@pytest.mark.parametrize("skew", [False, True])
def test_the_four_shares_add_up_to_the_uncut_layer(tiny, skew):
    """Four chips hold two of the eight experts each. Their routed parts,
    with the shared expert (which every chip computes alike) counted once,
    are what the uncut reference layer gives."""
    cfg, params = tiny
    p = _skewed(params["layers"][2], 6) if skew else params["layers"][2]
    h = jax.random.normal(jax.random.key(6), (40, cfg.d_model))
    whole, _ = ref.moe(h, p, ref_config(cfg), lambda a, w: a @ w)
    shared = (jax.nn.silu(h @ p["s_gate"]) * (h @ p["s_up"])) @ p["s_down"]
    routed, here = 0.0, 0
    for chip in range(4):
        share = dataclasses.replace(cfg, experts_held=2, expert_offset=2 * chip)
        held = {k: v[2 * chip : 2 * chip + 2] if k.startswith("e_") else v for k, v in p.items()}
        y, counts, _ = kl.moe_ffn(h, held, share)
        routed += y - shared
        here += int(counts[0])
        one, _ = ref.moe(h, held, ref_config(share), lambda a, w: a @ w)
        np.testing.assert_allclose(y, one, rtol=2e-4, atol=2e-6)  # a share alone, too
    assert here == 40 * cfg.experts_per_token  # every pick landed on exactly one chip
    np.testing.assert_allclose(routed + shared, whole, rtol=2e-4, atol=5e-6)


def test_balancing_the_selection_bias_evens_the_experts_load():
    """Random weights with a zero bias send every token to the same few
    experts; the published balancing rule spreads the picks, and changes the
    selection only (the weights of the chosen still come from the scores)."""
    cfg = kl.KimiLinearConfig.tiny()
    params = kl.draw_params(jax.random.key(2), cfg)
    assert all(float(jnp.abs(p["router_bias"]).max()) == 0 for p in params["layers"][1:])
    # at this size the experts are nearly even by themselves: tilt them as
    # the common part of the hidden states tilts them at the published widths
    tilt = jnp.linspace(-0.3, 0.3, cfg.n_experts)
    params["layers"] = [
        {**p, "router_bias": tilt} if "router_bias" in p else p for p in params["layers"]
    ]
    balanced = kl.balance_routers(params, jax.random.key(3), cfg, rounds=64, tokens=128)
    toks = jax.random.randint(jax.random.key(4), (1, 128), 0, cfg.vocab_size)

    def loads(ps):
        *_, picks = kl.paged_prefill(
            ps, toks, jnp.asarray(128), jnp.asarray(0), jnp.arange(1, 9), kl.init_pool(cfg, 9, 16, 0),
            cfg, block_size=16, with_picks=True,
        )
        return np.stack([np.bincount(np.asarray(l).reshape(-1), minlength=cfg.n_experts) for l in picks])

    before, after = loads(params), loads(balanced)
    assert before.sum() == after.sum() == cfg.n_moe_layers * 128 * cfg.experts_per_token
    assert (after.std(axis=1) < 0.5 * before.std(axis=1)).all(), (before, after)
    p, b = params["layers"][1], balanced["layers"][1]
    assert all(np.array_equal(p[k], b[k]) for k in p if k != "router_bias")
    h = jax.random.normal(jax.random.key(5), (8, cfg.d_model))
    idx, w = kl.route(h, b, cfg)
    s = jax.nn.sigmoid(h @ b["router"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    np.testing.assert_allclose(w, cfg.routed_scaling * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)


def test_pool_parts_and_what_the_paged_programs_refuse(tiny):
    cfg, _ = tiny
    pool = paged.init_block_pool(cfg, 9, 16, 6)
    Lk, Lm = len(cfg.kda_layers), len(cfg.mla_layers)
    H, d = cfg.kda_heads, cfg.kda_head_dim
    assert pool["ckv"].shape == (Lm, 9, 16, cfg.latent_dim)
    assert pool["state"].shape == (Lk, 7, H, d, d) and pool["state"].dtype == jnp.float32
    assert pool["conv"].shape == (Lk, 7, cfg.conv_kernel - 1, cfg.conv_dim)
    assert paged.init_block_pool(cfg, 9, 16)["state"].shape[1] == cfg.state_slots + 1
    assert paged.cache(cfg).slot_state and not paged.cache(LlamaConfig.tiny()).slot_state
    with pytest.raises(ValueError, match="recurrent state"):
        paged.paged_verify(None, jnp.zeros((1, 2), jnp.int32), None, None, pool, cfg, block_size=16)


@pytest.mark.parametrize("interpret", [False, True], ids=["gather", "kernel_interpreted"])
def test_paged_prefill_and_decode_are_the_reference_forward(tiny, interpret):
    """Two prompts in two buckets into two slots and scattered tables, three
    decode steps with a free and a prefilling-like (not live) slot beside
    them: logits against the reference's full forward, the latent layers'
    decode by the gather and by the kernel over live blocks (interpreted:
    on a TPU these rows of 576 would gather), the KDA layers' prefill scan by
    the plain loop and by its kernel (interpreted too)."""
    cfg, params = tiny
    c = ref_config(cfg)
    bs, W, B, K = 16, 8, 4, 3
    rng = np.random.default_rng(0)
    lens, slots = [50, 23], [2, 0]
    toks = rng.integers(0, cfg.vocab_size, size=(2, max(lens) + K)).astype(np.int32)
    want, inner = ref.forward(params, jnp.asarray(toks), c, inner=True)
    want_picks = inner["picks"]
    prefill = jax.jit(functools.partial(paged.paged_prefill, cfg=cfg, block_size=bs, interpret=interpret))
    decode = jax.jit(functools.partial(paged.paged_decode, cfg=cfg, block_size=bs, interpret=interpret))
    pool = paged.init_block_pool(cfg, 20, bs, B)
    # whatever was in the slots before must not matter
    pool["state"] = pool["state"] + 3.0
    pool["conv"] = pool["conv"] + 1.0
    free = list(rng.permutation(np.arange(1, 20)))
    tables = np.zeros((B, W), np.int32)
    for i, n in enumerate(lens):
        need = -(-(n + K) // bs)
        tables[slots[i], :need] = [free.pop() for _ in range(need)]
        bucket = 64 if n > 32 else 32
        t = np.zeros((1, bucket), np.int32)
        t[0, :n] = toks[i, :n]
        pool, logits, counts = prefill(
            params, jnp.asarray(t), jnp.asarray(n), jnp.asarray(0),
            jnp.asarray(tables[slots[i]]), pool, slot=jnp.asarray(slots[i]),
        )
        np.testing.assert_allclose(logits, want[i, n - 1], rtol=2e-3, atol=2e-5)
        assert counts.shape == (cfg.n_moe_layers, 2)
        assert counts[:, 0].tolist() == [n * cfg.experts_per_token] * cfg.n_moe_layers
    live = np.zeros(B, bool)
    live[slots] = True
    for k in range(K):
        last, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
        for i, n in enumerate(lens):
            last[slots[i]], pos[slots[i]] = toks[i, n + k], n + k
        pool, logits, counts = decode(
            params, jnp.asarray(last), jnp.asarray(pos), jnp.asarray(tables), pool,
            live=jnp.asarray(live),
        )
        for i, n in enumerate(lens):
            np.testing.assert_allclose(logits[slots[i]], want[i, n + k], rtol=2e-3, atol=2e-5)
        assert counts[:, 0].tolist() == [2 * cfg.experts_per_token] * cfg.n_moe_layers
    # the latent rows as they lie in the pool, gathered in the order of the positions
    for i, n in enumerate(lens):
        rows = np.asarray(pool["ckv"][:, tables[slots[i]]]).reshape(len(cfg.mla_layers), W * bs, -1)
        np.testing.assert_allclose(rows[:, : n + K], inner["latents"][:, i, : n + K], rtol=2e-3, atol=2e-5)
    # the routing of the prompt, as the benchmark's check compares it
    _, _, _, picks = kl.paged_prefill(
        params, jnp.asarray(toks[:1, :32]), jnp.asarray(32), jnp.asarray(0),
        jnp.asarray(tables[2]), paged.init_block_pool(cfg, 20, bs, B), cfg,
        block_size=bs, with_picks=True,
    )
    assert (np.sort(picks, -1) == np.sort(want_picks[:, 0, :32], -1)).all()
