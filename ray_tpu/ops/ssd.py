"""State-space duality (Mamba-2, arXiv:2405.21060): the recurrence of a
state-space layer with one scalar decay a head, on a state ``h`` of
``[P, N]`` a head (``P`` the head's channels, ``N`` the state size):

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t
    y_t = h_t C_t + D x_t

``A < 0`` and ``D`` are one number a head, ``dt_t > 0`` one a head and
position; ``B_t`` and ``C_t`` [N] are shared by the heads of a group (head
``h`` of ``H`` uses group ``h // (H / G)``).

Two forms of the same mathematics, as :mod:`ray_tpu.ops.delta_rule` has them:

- :func:`ssd_step`, one token a sequence, for decode: reads and writes the
  state once.
- :func:`ssd_chunked`, chunks of :data:`CHUNK` tokens (the published
  ``chunk_size``) under ``lax.scan``, for prefill. With ``L_t`` the running sum
  of ``dt A`` inside a chunk that starts from ``h_0``, position ``t`` sees
  ``exp(L_t) h_0`` and, of each earlier write ``s <= t``, ``exp(L_t - L_s)
  dt_s x_s (x) B_s``: outputs are one masked ``[C, C]`` product a head and one
  product with the chunk's first state, the next state one product more. Every
  decay is an ``exp`` of a sum at or below zero (``L_t - L_s`` with ``s <= t``,
  ``L_t``, ``L_C - L_s``): nothing is divided by a decay and nothing overflows.

A position with ``dt = 0`` leaves the state as it was (the padded tail of a
prefill bucket). State and accumulation are float32; plain ``jax.numpy``, no
kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 128
_F32 = jnp.float32
# The state is float32 and feeds back into itself: its products are taken at
# full precision (on a TPU the default rounds float32 operands to bfloat16).
_PREC = jax.lax.Precision.HIGHEST


def ssd_step(x, dt, A, B, C, D, h):
    """One token. ``x``: [..., H, P]; ``dt``: [..., H]; ``A``, ``D``: [H];
    ``B``, ``C``: [..., G, N]; ``h``: [..., H, P, N] float32. Returns
    ``(y [..., H, P] float32, h_t)``."""
    x, dt, A, B, C, D = (a.astype(_F32) for a in (x, dt, A, B, C, D))
    H, P = x.shape[-2:]
    G, N = B.shape[-2:]
    lead = x.shape[:-2]
    by_group = lambda a: a.reshape(*lead, G, H // G, *a.shape[len(lead) + 1 :])  # noqa: E731
    write = by_group(x * dt[..., None])[..., None] * B[..., :, None, None, :]
    h = jnp.exp(dt * A)[..., None, None] * h + write.reshape(*lead, H, P, N)
    y = jnp.sum(by_group(h) * C[..., :, None, None, :], axis=-1).reshape(*lead, H, P)
    return y + D[:, None] * x, h


def _chunk(h, inputs):
    """One chunk of every head: ``x`` [H, C, P], ``dt``, ``a = dt A`` [H, C],
    ``B``, ``C`` [G, C, N]; ``h`` [H, P, N]."""
    x, dt, a, B, C = inputs
    H, Q, P = x.shape
    G, _, N = B.shape
    L = jnp.cumsum(a, axis=1)  # [H, C], decreasing
    # seen[t, s] = exp(L_t - L_s) dt_s (C_t . B_s), wanted for s <= t only.
    decay = jnp.exp(jnp.minimum(L[:, :, None] - L[:, None, :], 0.0))
    cb = jnp.einsum("gtn,gsn->gts", C, B, precision=_PREC)
    seen = jnp.tril(decay * dt[:, None, :]).reshape(G, H // G, Q, Q) * cb[:, None]
    hg = h.reshape(G, H // G, P, N)
    y = jnp.einsum("hts,hsp->htp", seen.reshape(H, Q, Q), x, precision=_PREC) + (
        jnp.exp(L)[..., None]
        * jnp.einsum("gtn,ghpn->ghtp", C, hg, precision=_PREC).reshape(H, Q, P)
    )
    to_end = jnp.exp(L[:, -1:] - L) * dt  # exp(L_C - L_s) dt_s
    wrote = jnp.einsum(
        "ghsp,gsn->ghpn", (x * to_end[..., None]).reshape(G, H // G, Q, P), B, precision=_PREC
    )
    h = jnp.exp(L[:, -1])[:, None, None] * h + wrote.reshape(H, P, N)
    return h, y


def ssd_chunked(x, dt, A, B, C, D, h0):
    """A whole sequence. ``x``: [T, H, P]; ``dt``: [T, H]; ``A``, ``D``: [H];
    ``B``, ``C``: [T, G, N]; ``h0``: [H, P, N]. ``T`` need not be a multiple of
    the chunk: the tail is padded with positions that leave the state alone.
    Returns ``(y [T, H, P] float32, h_T float32)``."""
    T = x.shape[0]
    pad = -T % CHUNK
    n = (T + pad) // CHUNK

    def chunks(a):  # [T, K, ...] -> [n, K, CHUNK, ...], zero-padded
        a = jnp.pad(a.astype(_F32), [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        return jnp.moveaxis(a.reshape(n, CHUNK, *a.shape[1:]), 1, 2)

    xs, dts = chunks(x), chunks(dt)
    h, y = jax.lax.scan(
        _chunk, h0.astype(_F32), (xs, dts, dts * A.astype(_F32)[:, None], chunks(B), chunks(C))
    )
    H, P = y.shape[1], y.shape[3]  # y: [n, H, CHUNK, P]
    y = jnp.moveaxis(y, 2, 1).reshape(n * CHUNK, H, P)[:T]
    return y + D.astype(_F32)[:, None] * x.astype(_F32), h
