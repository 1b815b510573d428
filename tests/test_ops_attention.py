"""Flash-attention kernel correctness via pallas interpret mode on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
