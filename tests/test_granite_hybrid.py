"""Granite 4.0-H (``granitemoehybrid``) at a tiny size on the CPU, the published
*shape* kept: a period with the attention layer inside it turned twice (so the
scan turns), one group, every multiplier off 1. The paged programs against the
plain reference's full forward in float32 (a whole prompt, a prompt in two
chunks, decode, a slot kept); the scanned program against the same layers
walked singly; both kernels in the Pallas interpreter against their plain
forms; the reference's controls.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import granitemoehybrid_ref as ref  # noqa: E402
from ray_tpu.models import granite_hybrid as gh, latent_moe, paged  # noqa: E402
from ray_tpu.ops import paged_attention, state_step  # noqa: E402
from ray_tpu.ops.ssd import ssd_step  # noqa: E402
from test_tpu_aot import _pallas_calls  # noqa: E402

pytestmark = pytest.mark.timeout(300)

# Float32 on both sides: what is left is the order of the sums (the chunked
# scan against the token-by-token recurrence, the online softmax against the
# whole row), a few float32 roundings deep after eight layers.
RTOL, ATOL = 2e-3, 2e-5


def ref_config(cfg: gh.GraniteHybridConfig) -> dict:
    """The reference's dictionary of published keys for ``cfg``."""
    return dict(
        hidden_size=cfg.d_model, vocab_size=cfg.vocab_size, layer_types=list(cfg.layer_types),
        mamba_n_heads=cfg.mamba_heads, mamba_d_head=cfg.mamba_head_dim,
        mamba_n_groups=cfg.ssm_groups, mamba_d_state=cfg.ssm_state, mamba_d_conv=cfg.conv_kernel,
        num_attention_heads=cfg.n_head, num_key_value_heads=cfg.n_kv_head,
        attention_multiplier=cfg.attention_multiplier, embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier, logits_scaling=cfg.logits_scaling,
        rms_norm_eps=cfg.rms_eps,
    )


@pytest.fixture(scope="module")
def tiny():
    cfg = gh.GraniteHybridConfig.tiny()
    return cfg, gh.init_params(jax.random.key(0), cfg)


def test_the_tiny_size_has_the_published_shape(tiny):
    cfg, params = tiny
    assert cfg.period == ("mamba", "mamba", "attention", "mamba") and cfg.periods == 2
    assert cfg.ssm_groups == 1 and cfg.attention_multiplier != cfg.head_dim**-0.5
    assert 1 not in (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling)
    assert "lm_head" not in params and len(params["period"]) == 4
    assert params["period"][2]["wq"].shape[0] == 2
    full = gh.GraniteHybridConfig()
    assert len(full.period) == 10 and full.periods == 4 and full.period.index("attention") == 5
    assert gh.qk_std(full) == 0.0625


def _prefill(cfg, params, toks, pool, start=0, slot=0, **kw):
    T = len(toks)
    toks = [*toks, *[0] * (-T % 16)]  # a bucket of whole blocks, as the engine's are
    table = jnp.arange(1, 17, dtype=jnp.int32)
    return gh.paged_prefill(
        params, jnp.asarray([toks], jnp.int32), jnp.asarray(T, jnp.int32), jnp.asarray(start, jnp.int32),
        table, pool, cfg, block_size=16, slot=jnp.asarray(slot, jnp.int32), **kw,
    )


@pytest.mark.parametrize("how", ["whole", "two_chunks"])
def test_prefill_is_the_reference_forward(tiny, how):
    """A whole prompt, and the same prompt in two chunks, the second from the
    slot's state: last logits, state, tail and keys and values."""
    cfg, params = tiny
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, size=48).tolist()
    pool = gh.init_pool(cfg, 17, 16, 2)
    if how == "whole":
        pool, logits = _prefill(cfg, params, toks, pool)
    else:
        pool, _ = _prefill(cfg, params, toks[:32], pool)
        pool, logits = _prefill(cfg, params, toks[32:], pool, start=32)
    want, inner = ref.forward(params, jnp.asarray([toks], jnp.int32), ref_config(cfg), inner=True)
    np.testing.assert_allclose(logits, want[0, -1], rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(pool["state"][:, 0], inner["state"][:, 0], rtol=RTOL, atol=ATOL)
    tails = pool["conv"][:, 0].reshape(inner["conv"][:, 0].shape)  # a slot's tail is one flat row
    np.testing.assert_allclose(tails, inner["conv"][:, 0], rtol=RTOL, atol=ATOL)
    # [A, blocks, KH, block, v | k] -> [A, positions, k | v of every head]
    rows = pool["kv"][:, 1:4].transpose(0, 1, 3, 2, 4).reshape(2, 48, cfg.n_kv_head, 2 * cfg.head_dim)
    lie = jnp.concatenate([rows[..., cfg.head_dim:].reshape(2, 48, -1), rows[..., : cfg.head_dim].reshape(2, 48, -1)], -1)
    np.testing.assert_allclose(lie, inner["kv"][:, 0], rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("interpret", [False, True], ids=["plain", "kernels-interpreted"])
def test_decode_is_the_reference_forward_and_a_kept_slot_stays(tiny, interpret):
    """Three decode steps behind a prefill, with the gather and the plain step
    and with both kernels in the interpreter; slot 1 is not live and keeps its
    state and tail bit for bit."""
    cfg, params = tiny
    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab_size, size=40).tolist()
    pool = gh.init_pool(cfg, 17, 16, 2)
    pool = {**pool, "state": pool["state"].at[:, 1].set(0.5), "conv": pool["conv"].at[:, 1].set(0.25)}
    pool, _ = _prefill(cfg, params, toks[:37], pool)
    tables = jnp.stack([jnp.arange(1, 9), jnp.zeros(8, jnp.int32)]).astype(jnp.int32)
    live = jnp.asarray([True, False])
    got = []
    for i in range(37, 40):
        pool, logits = gh.paged_decode(
            params, jnp.asarray([toks[i], 0], jnp.int32), jnp.asarray([i, 0], jnp.int32), tables, pool,
            cfg, block_size=16, live=live, interpret=interpret,
        )
        got.append(logits[0])
    want, inner = ref.forward(params, jnp.asarray([toks], jnp.int32), ref_config(cfg), inner=True)
    np.testing.assert_allclose(jnp.stack(got), want[0, 37:], rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(pool["state"][:, 0], inner["state"][:, 0], rtol=RTOL, atol=ATOL)
    np.testing.assert_array_equal(pool["state"][:, 1], jnp.full_like(pool["state"][:, 1], 0.5))
    np.testing.assert_array_equal(pool["conv"][:, 1], jnp.full_like(pool["conv"][:, 1], 0.25))


def test_the_scanned_programs_are_the_layers_walked_singly(tiny):
    cfg, params = tiny
    toks = np.random.default_rng(2).integers(0, cfg.vocab_size, size=33).tolist()
    zero = gh.init_pool(cfg, 17, 16, 2)
    pools, logits = zip(*(_prefill(cfg, params, toks, zero, unrolled=u) for u in (False, True)))
    np.testing.assert_allclose(logits[0], logits[1], rtol=1e-5, atol=1e-6)
    tables = jnp.arange(1, 17, dtype=jnp.int32).reshape(2, 8)
    step = functools.partial(
        gh.paged_decode, params, jnp.asarray([5, 7], jnp.int32), jnp.asarray([33, 0], jnp.int32), tables,
        cfg=cfg, block_size=16,
    )
    (a, la), (b, lb) = step(pools[0]), step(pools[0], unrolled=True)
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)
    for part in a:
        np.testing.assert_allclose(a[part], b[part], rtol=1e-5, atol=1e-6)


def test_the_decode_programs_jaxpr_holds_one_periods_calls():
    """At the published pattern the scanned decode program has nine state
    steps and one attention call, whatever the depth; walked singly, 36 and
    4."""
    cfg = gh.GraniteHybridConfig.tiny(layer_types=gh.PUBLISHED_LAYER_TYPES, max_seq=64)
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    params = jax.eval_shape(lambda k: gh.init_params(k, cfg), jax.random.key(0))
    pool = jax.eval_shape(lambda: gh.init_pool(cfg, 9, 16, 2))

    def names(unrolled):
        jaxpr = jax.make_jaxpr(functools.partial(
            gh.paged_decode, cfg=cfg, block_size=16, interpret=True, unrolled=unrolled))(
            params, sds((2,), i32), sds((2,), i32), sds((2, 4), i32), pool)
        return sorted(eqn.params["name"] for eqn in _pallas_calls(jaxpr.jaxpr))

    assert names(False) == ["paged_decode_attention_packed"] + ["state_step_ssd"] * 9
    assert names(True) == ["paged_decode_attention_packed"] * 4 + ["state_step_ssd"] * 36


@pytest.mark.parametrize("scale", [None, 1 / 64], ids=["sqrt", "stated"])
def test_the_packed_kernel_at_64_wide_heads_is_the_gather(scale):
    """Eight KV heads of four queries, heads of 64, value and key in one row of
    128 lanes: the interpreted kernel against the gather, at the stated scale
    and at the default one; lengths on and off a block's and a chunk's edge."""
    rng = np.random.default_rng(3)
    B, KH, G, Dh, bs, W, N, L = 5, 8, 4, 64, 16, 24, 131, 2
    pool = jnp.asarray(rng.standard_normal((L, N, KH, bs, 2 * Dh)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, KH, G, Dh)) * 4, jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, N))[: B * W].reshape(B, W), jnp.int32)
    lengths = jnp.asarray([1, 16, 255, 257, 384], jnp.int32)
    kind = paged.AttentionKind(KH, Dh, Dh, 4, scale=scale, packed=True)
    layer = jnp.asarray(1, jnp.int32)
    got = paged.packed_decode_attention(kind, bs, None, True)(q, pool, layer, tables, lengths)
    want = paged.packed_decode_attention(kind, bs, None, False)(q, pool, layer, tables, lengths)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    other = paged._attend_packed_gathered(q, pool, layer, tables, lengths, scale=1 / 8 if scale else 1 / 64)
    assert np.abs(np.asarray(other - want)).max() > 1e-2  # the scale is in the answer
    assert paged_attention.fits_packed(KH, Dh, bs, 2) and not paged_attention.fits(KH, Dh, bs, 2)


def test_the_per_head_kernel_takes_a_stated_scale():
    rng = np.random.default_rng(4)
    B, KH, G, Dh, bs, W, N = 3, 2, 4, 128, 16, 8, 33
    pk, pv = (jnp.asarray(rng.standard_normal((1, N, KH, bs, Dh)), jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, KH, G, Dh)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, N))[: B * W].reshape(B, W), jnp.int32)
    lengths = jnp.asarray([3, 64, 100], jnp.int32)
    kind = paged.AttentionKind(KH, Dh, Dh, 4, scale=1 / 32)
    args = (q, pk, pv, jnp.asarray(0, jnp.int32), tables, lengths)
    got = paged.decode_attention(kind, bs, None, True)(*args)
    np.testing.assert_allclose(got, paged._attend_gathered(*args, scale=1 / 32), rtol=2e-5, atol=2e-5)
    plain = paged.decode_attention(paged.AttentionKind(KH, Dh, Dh, 4), bs, None, True)(*args)
    np.testing.assert_allclose(plain, paged._attend_gathered(*args), rtol=2e-5, atol=2e-5)


def test_the_state_step_kernel_at_one_group_of_64_heads_is_the_plain_step():
    """``state_step.ssd`` in the interpreter at the published state: 64 heads
    of 64 x 128 in ONE group (a block is all 64 heads), three rows of which
    one is kept."""
    rng = np.random.default_rng(5)
    rows, H, P, N = 3, 64, 64, 128
    assert state_step.tiles(H, P, N) and state_step.head_group(H, P, N) == 64
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    state = f(2, rows + 1, H, P, N)
    x, dt, B, C = f(rows, H, P), jnp.abs(f(rows, H)) * 0.1, f(rows, 1, N), f(rows, 1, N)
    A, D = -jnp.abs(f(H)), f(H)
    keep = jnp.asarray([False, True, False])
    y, held = state_step.ssd(x, dt, A, B, C, D, state_step.Rows(state, 1, rows, keep, interpret=True))
    want_y, want_h = ssd_step(x, dt, A, B, C, D, state[1, :rows])
    live = ~np.asarray(keep)
    np.testing.assert_allclose(y[live], want_y[live], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(held.state[1, :rows][live], want_h[live], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(held.state[1, 1], state[1, 1])
    np.testing.assert_array_equal(held.state[0], state[0])
    np.testing.assert_array_equal(held.state[1, rows], state[1, rows])


def test_outputs_with_no_expert_layer_are_the_pool_and_the_logits():
    pool, logits = {"kv": jnp.zeros(3)}, jnp.ones(4)
    assert latent_moe.outputs(pool, logits, [], False) == (pool, logits)
    assert latent_moe.outputs(pool, logits, [], True) == (pool, logits)
    seen = [(jnp.ones(2, jnp.int32), jnp.ones((5, 3), jnp.int32))]
    assert len(latent_moe.outputs(pool, logits, seen, False)) == 3
    assert len(latent_moe.outputs(pool, logits, seen, True)) == 4


@pytest.mark.parametrize("wrong", ref.WRONGS)
def test_each_control_of_the_reference_moves_the_logits(tiny, wrong):
    cfg, params = tiny
    toks = jnp.asarray([np.random.default_rng(6).integers(0, cfg.vocab_size, size=24)], jnp.int32)
    c = ref_config(cfg)
    want = ref.forward(params, toks, c)
    got = ref.forward(params, toks, c, wrong=wrong)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) > 2e-3  # a thousand float32 roundings
    at = ref.forward(params, toks, c, logits_at=[[3, 23]])
    np.testing.assert_allclose(at[0], want[0, [3, 23]], rtol=1e-6, atol=1e-6)
