"""The state-space recurrence on the CPU: the chunked form against the
one-token form applied token by token, across chunk boundaries, from a state
that is not zero, with a padded tail, at lengths that are no multiple of the
chunk; and the one-token form against the recurrence written out.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssd

H, G, P, N = 4, 2, 8, 16


def _inputs(key, T, strong_decay=False):
    ks = jax.random.split(key, 8)
    x = jax.random.normal(ks[0], (T, H, P))
    # steps from 0.001 to 0.1 as the initialiser draws them, rates 1 to 16;
    # "strong" is past what exp(-sum) over a chunk could be divided by
    lo, hi = (1.0, 4.0) if strong_decay else (0.001, 0.1)
    dt = jax.random.uniform(ks[1], (T, H), minval=lo, maxval=hi)
    A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    B = jax.random.normal(ks[3], (T, G, N))
    C = jax.random.normal(ks[4], (T, G, N))
    D = jax.random.normal(ks[5], (H,))
    h0 = jax.random.normal(ks[6], (H, P, N))
    return x, dt, A, B, C, D, h0


def _token_by_token(x, dt, A, B, C, D, h):
    ys = []
    for t in range(x.shape[0]):
        y, h = ssd.ssd_step(x[t], dt[t], A, B[t], C[t], D, h)
        ys.append(y)
    return jnp.stack(ys), h


def test_step_is_the_recurrence_written_out():
    x, dt, A, B, C, D, h0 = _inputs(jax.random.key(0), 1)
    y, h = ssd.ssd_step(x[0], dt[0], A, B[0], C[0], D, h0)
    x, dt, A, B, C, D, h0 = (np.asarray(a, np.float64) for a in (x, dt, A, B, C, D, h0))
    for head in range(H):
        g = head // (H // G)
        want = np.exp(dt[0, head] * A[head]) * h0[head] + dt[0, head] * np.outer(x[0, head], B[0, g])
        np.testing.assert_allclose(h[head], want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            y[head], want @ C[0, g] + D[head] * x[0, head], rtol=1e-5, atol=1e-5
        )


@pytest.mark.parametrize("T", [1, 5, 127, 128, 129, 300, 384])
@pytest.mark.parametrize("from_zero", [True, False], ids=["zero_state", "a_state"])
def test_chunked_is_the_step_token_by_token(T, from_zero):
    x, dt, A, B, C, D, h0 = _inputs(jax.random.key(T), T)
    if from_zero:
        h0 = jnp.zeros_like(h0)
    y_c, h_c = ssd.ssd_chunked(x, dt, A, B, C, D, h0)
    y_s, h_s = _token_by_token(x, dt, A, B, C, D, h0)
    assert y_c.shape == (T, H, P) and y_c.dtype == jnp.float32 and h_c.dtype == jnp.float32
    np.testing.assert_allclose(y_c, y_s, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h_c, h_s, rtol=2e-4, atol=2e-4)


def test_chunked_survives_decays_no_product_could_be_divided_by():
    x, dt, A, B, C, D, h0 = _inputs(jax.random.key(3), 200, strong_decay=True)
    y_c, h_c = ssd.ssd_chunked(x, dt, A, B, C, D, h0)
    y_s, h_s = _token_by_token(x, dt, A, B, C, D, h0)
    assert bool(jnp.all(jnp.isfinite(y_c))) and bool(jnp.all(jnp.isfinite(h_c)))
    np.testing.assert_allclose(y_c, y_s, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h_c, h_s, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("T,real", [(128, 27), (256, 128), (256, 131), (64, 0)])
def test_a_padded_tail_leaves_the_state_as_it_was(T, real):
    x, dt, A, B, C, D, h0 = _inputs(jax.random.key(11), T)
    live = (jnp.arange(T) < real)[:, None]
    y_pad, h_pad = ssd.ssd_chunked(x, dt * live, A, B, C, D, h0)
    if real == 0:
        np.testing.assert_allclose(h_pad, h0, rtol=1e-6, atol=1e-6)
        return
    y_cut, h_cut = ssd.ssd_chunked(x[:real], dt[:real], A, B[:real], C[:real], D, h0)
    np.testing.assert_allclose(h_pad, h_cut, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y_pad[:real], y_cut, rtol=1e-5, atol=1e-5)


def test_two_runs_continue_where_one_whole_run_goes():
    x, dt, A, B, C, D, h0 = _inputs(jax.random.key(5), 260)
    y, h = ssd.ssd_chunked(x, dt, A, B, C, D, h0)
    y1, h1 = ssd.ssd_chunked(x[:150], dt[:150], A, B[:150], C[:150], D, h0)
    y2, h2 = ssd.ssd_chunked(x[150:], dt[150:], A, B[150:], C[150:], D, h1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h2, h, rtol=2e-4, atol=2e-4)


def test_step_takes_a_batch_of_slots():
    x, dt, A, B, C, D, h0 = _inputs(jax.random.key(7), 3)
    hs = jnp.stack([h0, 2 * h0, jnp.zeros_like(h0)])
    y, h = ssd.ssd_step(x, dt, A, B, C, D, hs)
    for b in range(3):
        y1, h1 = ssd.ssd_step(x[b], dt[b], A, B[b], C[b], D, hs[b])
        np.testing.assert_allclose(y[b], y1, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(h[b], h1, rtol=1e-6, atol=1e-6)
