"""Flash-attention kernel correctness via pallas interpret mode on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.ops.attention import causal_attention


def _qkv(key, B=2, H=2, S=128, D=32, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    shape = (B, H, S, D)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def test_flash_matches_reference_forward():
    q, k, v = _qkv(jax.random.key(0))
    ref = causal_attention(q, k, v, impl="reference")
    flash = causal_attention(
        q, k, v, impl="pallas", block_q=32, block_k=32, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(flash), np.asarray(ref), rtol=1e-4, atol=1e-4
    )


def test_flash_uneven_diag_blocks():
    # block_q != block_k exercises the diagonal-straddling mask logic.
    q, k, v = _qkv(jax.random.key(1), S=96, D=16)
    ref = causal_attention(q, k, v, impl="reference")
    flash = causal_attention(
        q, k, v, impl="pallas", block_q=32, block_k=48, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(flash), np.asarray(ref), rtol=1e-4, atol=1e-4
    )


def test_flash_gradients_match_reference():
    q, k, v = _qkv(jax.random.key(2), B=1, H=2, S=64, D=16)

    def loss_ref(q, k, v):
        return jnp.sum(causal_attention(q, k, v, impl="reference") ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(
            causal_attention(
                q, k, v, impl="pallas", block_q=32, block_k=32, interpret=True
            )
            ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3
        )


def test_flash_gradients_uneven_diag_blocks():
    # block_q != block_k exercises the straddling mask in both bwd kernels.
    q, k, v = _qkv(jax.random.key(4), B=1, H=1, S=96, D=16)

    def loss(impl, **kw):
        def f(q, k, v):
            return jnp.sum(causal_attention(q, k, v, impl=impl, **kw) ** 2)

        return f

    g_ref = jax.grad(loss("reference"), argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(
        loss("pallas", block_q=32, block_k=48, interpret=True),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3
        )


def test_explicit_pallas_rejects_indivisible_seq():
    q, k, v = _qkv(jax.random.key(3), S=100, D=16)
    with pytest.raises(ValueError, match="divisible"):
        causal_attention(q, k, v, impl="pallas", block_q=32, block_k=32)


def test_flash_per_shard_under_a_mesh_matches_reference(devices8):
    """Under a mesh the kernels run per shard (batch over dp/fsdp, heads
    over tp); values and gradients are those of the unsharded reference."""
    from ray_tpu.parallel import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2), devices8)
    q, k, v = _qkv(jax.random.key(5), B=4, H=4, S=64, D=16)

    def loss(impl, **kw):
        def f(q, k, v):
            return jnp.sum(causal_attention(q, k, v, impl=impl, **kw) ** 2)

        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))

    ref, g_ref = loss("reference")(q, k, v)
    out, g = loss(
        "pallas", block_q=32, block_k=32, interpret=True, mesh=mesh
    )(q, k, v)
    np.testing.assert_allclose(float(out), float(ref), rtol=1e-4)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3
        )


def _value_and_grads(impl, **kw):
    def f(q, k, v):
        return jnp.sum(causal_attention(q, k, v, impl=impl, **kw) ** 2)

    return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))


# (block_q, block_k, S, rows to a group of a diagonal tile; None: scored whole)
_DIAGONAL_CASES = [
    pytest.param(256, 256, 512, 128, id="2-groups-2-blocks"),
    pytest.param(256, 256, 1024, 128, id="2-groups-4-blocks"),
    pytest.param(512, 512, 1024, 256, id="2-groups-of-256"),
    pytest.param(1024, 1024, 1024, 512, id="2-groups-of-512-1-block"),
    pytest.param(2048, 2048, 2048, 512, id="4-groups-1-block"),
    pytest.param(2048, 2048, 4096, 512, id="4-groups-2-blocks"),
    pytest.param(128, 256, 512, None, id="whole-blocks-differ"),
    pytest.param(256, 128, 512, None, id="whole-blocks-differ-the-other-way"),
    pytest.param(128, 128, 256, None, id="whole-one-lane-tile"),
]


@pytest.mark.parametrize("block_q, block_k, S, group", _DIAGONAL_CASES)
def test_flash_diagonal_tile_forward(block_q, block_k, S, group):
    """A tile on the diagonal cut into row groups that see only their own
    columns (equal blocks that halve into whole lane tiles), or scored whole
    under the mask (every other shape), gives the reference's values."""
    assert attention.diag_group(block_q, block_k) == group
    q, k, v = _qkv(jax.random.key(S + block_q), B=1, H=1 if S >= 4096 else 2, S=S, D=16)
    ref = causal_attention(q, k, v, impl="reference")
    flash = causal_attention(
        q, k, v, impl="pallas", block_q=block_q, block_k=block_k, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(flash), np.asarray(ref), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("block_q, block_k, S, group", _DIAGONAL_CASES)
def test_flash_diagonal_tile_gradients(block_q, block_k, S, group):
    """The fused backward over the same cut: column groups against the query
    rows at or after them."""
    assert attention.diag_group(block_q, block_k) == group
    q, k, v = _qkv(jax.random.key(S + block_k + 1), B=1, H=1 if S >= 4096 else 2, S=S, D=16)
    _, g_ref = _value_and_grads("reference")(q, k, v)
    _, g_flash = _value_and_grads(
        "pallas", block_q=block_q, block_k=block_k, interpret=True
    )(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3
        )


def test_flash_diagonal_groups_per_shard_under_a_mesh(devices8):
    """The cut tile per shard: blocks of 256 in two groups, two blocks."""
    from ray_tpu.parallel import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2), devices8)
    assert attention.diag_group(256, 256) == 128
    q, k, v = _qkv(jax.random.key(6), B=4, H=4, S=512, D=16)
    ref, g_ref = _value_and_grads("reference")(q, k, v)
    out, g = _value_and_grads(
        "pallas", block_q=256, block_k=256, interpret=True, mesh=mesh
    )(q, k, v)
    np.testing.assert_allclose(float(out), float(ref), rtol=1e-4)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3
        )


@pytest.mark.parametrize(
    "S, block, group, computed",
    [
        # One interior tile and two straddling ones a head, each scored whole.
        (1024, 512, None, 3 * 512 * 512),
        (1024, 512, 256, 512 * 512 + 2 * 3 * 256 * 256),
        (1024, 512, 128, 512 * 512 + 2 * 10 * 128 * 128),
        # One block a head: the groups' widths alone decide.
        (1024, 1024, 128, 36 * 128 * 128),
        # 8 of 36 tiles straddle.
        (4096, 512, None, 36 * 512 * 512),
        (4096, 512, 128, 28 * 512 * 512 + 8 * 10 * 128 * 128),
    ],
)
def test_causal_pairs_computed_over_needed(S, block, group, computed):
    assert attention.causal_pairs(S, block, block, group) == (
        computed, S * (S + 1) // 2
    )


def test_causal_pairs_at_the_train_cell():
    """S=1,024: 1.50 times the pairs needed under blocks of 512 with a
    straddling tile scored whole, 1.125 in groups of 128; what the kernels
    choose (PERF.md section 6, PR 41: the larger group is the faster one)
    scores 1.25 under blocks of 512 and 1.50 under one block of 1,024."""
    for block, group, ratio in (
        (512, None, 1.50), (512, 128, 1.125),
        (512, attention.diag_group(512, 512), 1.25),
        (1024, attention.diag_group(1024, 1024), 1.50),
    ):
        computed, needed = attention.causal_pairs(1024, block, block, group)
        assert computed / needed == pytest.approx(ratio, abs=2e-3)
    # Blocks larger than the sequence are clamped, as the dispatch clamps them.
    assert attention.causal_pairs(256, 512, 512, None) == (256 * 256, 256 * 257 // 2)


@pytest.mark.parametrize(
    "block, S, fitted",
    [(1024, 1024, 1024), (1024, 512, 512), (1024, 1536, 512), (512, 1536, 512),
     (1024, 2560, 512), (1024, 1280, 256), (32, 96, 32), (48, 96, 48), (32, 100, 32)],
)
def test_a_block_is_fitted_to_the_sequence(block, S, fitted):
    """Clamped, then halved while it does not divide: a length that blocks of
    512 divided keeps the kernel when 1,024 is asked for."""
    assert attention._fit_block(block, S) == fitted
