"""Gated delta rule with a decay per key channel (KDA, Kimi Linear,
arXiv:2510.26692): the recurrence of a linear-attention layer, per head, on
a state ``S`` of ``[d_k, d_v]``:

    S' = Diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

``alpha_t = exp(g_t)`` with ``g_t <= 0`` per key channel; ``beta_t`` in (0, 1)
(Kimi Linear) or in (0, 2) (Solar Open 2, ``kda_allow_neg_eigval``), the
caller's choice. With ``|k_t| = 1``, as both callers normalise it, the step's
transition ``(I - beta_t k_t k_t^T) Diag(alpha_t)`` has the eigenvalue ``1 -
beta_t`` along ``k_t``, in (-1, 1) for ``beta_t`` in (0, 2), and no singular
value above one: past 1 the state's part along ``k_t`` changes sign and is
still not grown.

Two forms of the same mathematics:

- :func:`kda_step`, one token a sequence, for decode: reads and writes the
  state once.
- :func:`kda_chunked`, chunks of :data:`CHUNK` tokens under ``lax.scan``, for
  prefill. Inside a chunk that starts from ``S_0`` the writes ``u_t = beta_t
  (v_t - S'^T_t k_t)`` satisfy a unit lower-triangular system ``(I + A) u =
  rhs`` (the WY / UT form), which is solved exactly, whatever ``beta`` is:
  below. Outputs and the next state are then matrix products. With ``G_t`` the
  running sum of ``g`` inside the chunk, every decay that appears is
  ``exp(G_t - G_i)`` with ``i <= t``, ``exp(G_t)`` or ``exp(G_C - G_i)``:
  exponents at or below zero, so nothing is ever divided by a product of
  ``alpha`` and nothing overflows (the writes ``u_t`` are the recurrence's
  own, and it grows nothing: above); a product that underflows is one the
  recurrence would have lost too.

The system's entries under the diagonal are ``A[t, i] = beta_t (k_t . k_i)``
decayed, under 2 in size here. ``I + A`` is inverted and the inverse applied
as a product (:func:`_unit_lower_inverse`); no solver is called and no series
is cut short. The four diagonal blocks of :data:`BLOCK` rows are inverted by
elimination a column at a time, the arithmetic of substitution with every
block of every head side by side: fifteen steps of one multiply and subtract
over ``[H, 4, 16, 16]``. Then ``[[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R
P^-1, Q^-1]]`` twice, 16 -> 32 -> 64, with products at full precision. Both
are identities of any unit lower-triangular matrix: nothing is assumed of the
entries. (On random entries up to 2, whose inverse reaches 1e10, the merging
products lose a few times what substitution loses, of the same three digits
of the largest entry; on this recurrence's systems, whose inverses are
bounded because the recurrence grows nothing, the result is as near the
token-by-token form as substitution's was at every decay the initialiser
gives, ``beta`` at 1.99 on keys a few degrees apart included:
``tests/test_kimi_linear.py``.) The system needs only ``k, g, beta``, so it
could be inverted for every chunk of a call ahead of the scan and the loop
left with the state's products; on a v5e that was slower than the parent's
solve inside the loop, because every intermediate the size of ``k`` then goes
out to the device's memory and comes back (PERF.md section 6, PR 45). So all
of it runs inside the scan, a chunk a step. What keeps a chunk's values on
the chip is a kernel, and it exists (PR 54): :mod:`ray_tpu.ops.delta_scan`,
the same mathematics as one Pallas call a layer, which a prefill program
lowered for a TPU runs in this form's place (``models/paged.py:
state_prefill``). This form stays the definition: the CPU, a mesh, widths
that are not a lane tile, and the tests' other arm.

The pair terms ``kk[t, i] = k_t . (k_i exp(G_t - G_i))`` and ``qk[t, i]``
(``q_t`` for ``k_t``), ``i <= t``, carry a decay per key channel, so they are
not one product of ``k`` with itself. They are formed by row blocks of
:data:`BLOCK` positions (:func:`_pair_terms`); for the block that starts at
position ``r``:

- columns before it (``i < r <= t``): ``exp(G_t - G_i) = exp(G_t - G_r)
  exp(G_r - G_i)``, so ``kk[t, i] = (k_t e^(G_t - G_r)) . (k_i e^(G_r - G_i))``:
  one ``[BLOCK, d_k] x [d_k, r]`` product a head, its operands the size of
  ``k``. ``G`` decreases, so both exponents are at or below zero, as every
  exponent above; columns at or past ``r`` are masked out of the product and
  their exponent clamped at zero before the ``exp``. A factor that underflows
  stands under a product ``exp(G_t - G_i)`` no larger than itself: one the
  elementwise form, and the recurrence, would have lost too.
- its own columns (``r <= i <= t``): elementwise, ``exp(G_t - G_i)`` a pair
  and channel over ``[H, BLOCK, BLOCK, d_k]``, then summed over the channel.
  (A product here would need ``exp(G_r - G_i)`` with ``i > r``, an exponent
  above zero: up to 15 positions' decay, past float32 at 6 a position.)

So no value has the ``[H, CHUNK, CHUNK, d_k]`` elements of a decay for every
pair (``BLOCK / CHUNK`` of them), and the rest runs on the matrix unit.
``BLOCK = 16`` is what flash-linear-attention's ``chunk_kda`` / ``chunk_gla``
intra-chunk kernels use; on a v5e at ``[2048, 64, 128]`` it was timed against
8, 32 and 64 (``tools/delta_rule_chip.py``; PERF.md section 6, PR 44).

A position with ``beta = 0`` and ``g = 0`` leaves the state as it was (the
padded tail of a prefill bucket). State and accumulation are float32; this
module is plain ``jax.numpy``, and the kernel beside it is held to it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64
BLOCK = 16  # rows of a block of the pair terms; divides CHUNK
_F32 = jnp.float32
# The state is float32 and feeds back into itself: its products are taken at
# full precision (on a TPU the default rounds float32 operands to bfloat16).
# They are under a tenth of a KDA layer's operations.
_PREC = jax.lax.Precision.HIGHEST


def kda_step(q, k, v, g, beta, S):
    """One token. ``q, k, g``: [..., d_k]; ``v``: [..., d_v]; ``beta``:
    [...]; ``S``: [..., d_k, d_v] float32. Returns ``(o [..., d_v] float32,
    S_t)``."""
    q, k, v, g = (a.astype(_F32) for a in (q, k, v, g))
    beta = beta.astype(_F32)
    S = jnp.exp(g)[..., None] * S
    u = beta[..., None] * (v - jnp.einsum("...kv,...k->...v", S, k, precision=_PREC))
    S = S + k[..., None] * u[..., None, :]
    return jnp.einsum("...kv,...k->...v", S, q, precision=_PREC), S


def _pair_terms(q, k, G):
    """``kk[t, i] = k_t . (k_i exp(G_t - G_i))`` and ``qk[t, i]`` with ``q_t``
    for ``k_t``, both ``[H, C, C]`` and meant for ``i <= t`` (the caller's
    ``tril``), by row blocks of :data:`BLOCK` positions: the module docstring
    says how."""
    H, C, d = k.shape
    n = C // BLOCK
    blocks = lambda a: a.reshape(H, n, BLOCK, d)  # noqa: E731
    Gb, kb, qb = blocks(G), blocks(k), blocks(q)
    Gr = Gb[:, :, :1]  # G at each block's first position r
    # Columns before the block: (x_t e^(G_t - G_r)) . (k_i e^(G_r - G_i)), one
    # product for the rows of k and of q.
    before = jnp.arange(C)[None, :] < jnp.arange(0, C, BLOCK)[:, None]  # [n, C(i)]
    to_r = jnp.where(before[..., None], jnp.exp(jnp.minimum(Gr - G[:, None], 0.0)), 0.0)
    from_r = jnp.exp(Gb - Gr)
    pair = jnp.einsum(
        "hbtc,hbic->hbti",
        jnp.concatenate([kb * from_r, qb * from_r], axis=2), k[:, None] * to_r,
        precision=_PREC,
    )  # [H, n, 2 B(t), C(i)]
    # The block's own columns, elementwise: exp(G_t - G_i) a channel, for i <= t.
    decay = jnp.exp(jnp.minimum(Gb[:, :, :, None] - Gb[:, :, None], 0.0))
    kd = decay * kb[:, :, None]  # [H, n, B(t), B(i), d]: k_i as position t sees it
    # Two sums over one kd, not one over stacked rows: XLA then fuses both with
    # the exp into one pass, and writes kd out between them otherwise.
    own = jnp.concatenate(
        [jnp.sum(x[:, :, :, None] * kd, axis=-1) for x in (kb, qb)], axis=2
    )  # [H, n, 2 B(t), B(i)]
    at_own = jnp.eye(n, dtype=_F32)[:, None, :, None]  # [n, 1, n, 1]: block b's columns
    pair = pair + (own[..., None, :] * at_own).reshape(pair.shape)
    return pair[:, :, :BLOCK].reshape(H, C, C), pair[:, :, BLOCK:].reshape(H, C, C)


def _mm(a, b):
    return jnp.matmul(a, b, precision=_PREC)


def _diag_block_inverses(A):
    """``A``: [..., C, C]. The inverse of ``I + N`` for each of the ``C /
    BLOCK`` diagonal blocks ``N`` of ``A``, of which only the entries under
    the diagonal are read: [..., C / BLOCK, BLOCK, BLOCK]. By elimination, a
    column at a time: ``I + N`` is the product over ``j`` of ``I + N[:, j]
    e_j^T``, whose inverses are ``I - N[:, j] e_j^T``, so ``X <- X - N[:, j]
    X[j, :]`` from ``X = I``; row ``j`` is final by then, since only columns
    before ``j`` reach it. The arithmetic of substitution, every block of
    every head side by side."""
    C = A.shape[-1]
    N = jnp.tril(
        jnp.stack([A[..., r:r + BLOCK, r:r + BLOCK] for r in range(0, C, BLOCK)], axis=-3), -1
    )

    def eliminate(j, X):
        column = jax.lax.dynamic_slice_in_dim(N, j, 1, axis=-1)
        return X - column * jax.lax.dynamic_slice_in_dim(X, j, 1, axis=-2)

    return jax.lax.fori_loop(
        0, BLOCK - 1, eliminate, jnp.broadcast_to(jnp.eye(BLOCK, dtype=_F32), N.shape)
    )


def _unit_lower_inverse(A):
    """``(I + A)^-1`` for ``A`` [..., C, C] strictly lower triangular, exactly
    (no series is cut short, nothing is assumed of the entries): the diagonal
    blocks of :data:`BLOCK` rows by elimination, then ``[[P, 0], [R, Q]]^-1 =
    [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]`` with products, the block doubling
    until it is the chunk."""
    C = A.shape[-1]
    inv, b = _diag_block_inverses(A), BLOCK
    while b < C:
        R = jnp.stack([A[..., r + b:r + 2 * b, r:r + b] for r in range(0, C, 2 * b)], axis=-3)
        pairs = inv.reshape(*inv.shape[:-3], -1, 2, b, b)
        P, Q = pairs[..., 0, :, :], pairs[..., 1, :, :]
        inv = jnp.concatenate(
            [
                jnp.concatenate([P, jnp.zeros_like(P)], axis=-1),
                jnp.concatenate([-_mm(_mm(Q, R), P), Q], axis=-1),
            ],
            axis=-2,
        )
        b *= 2
    return inv[..., 0, :, :]


def _chunk(S, inputs):
    """One chunk of every head: ``q, k, g`` [H, C, d_k], ``v`` [H, C, d_v],
    ``beta`` [H, C], ``S`` [H, d_k, d_v]."""
    q, k, v, g, beta = inputs
    G = jnp.cumsum(g, axis=1)  # [H, C, d_k], decreasing
    kk, qk = _pair_terms(q, k, G)
    eG = jnp.exp(G)
    inverse = _unit_lower_inverse(jnp.tril(kk, -1) * beta[..., None])
    rhs = beta[..., None] * (
        v - jnp.einsum("htc,hcv->htv", k * eG, S, precision=_PREC)
    )
    u = _mm(inverse, rhs)
    o = jnp.einsum("htc,hcv->htv", q * eG, S, precision=_PREC) + jnp.einsum(
        "hti,hiv->htv", jnp.tril(qk), u, precision=_PREC,
    )
    to_end = jnp.exp(G[:, -1:, :] - G)  # exp(G_C - G_i)
    S = eG[:, -1, :, None] * S + jnp.einsum(
        "hic,hiv->hcv", k * to_end, u, precision=_PREC
    )
    return S, o


def kda_chunked(q, k, v, g, beta, S0):
    """A whole sequence. ``q, k, g``: [T, H, d_k]; ``v``: [T, H, d_v];
    ``beta``: [T, H]; ``S0``: [H, d_k, d_v]. ``T`` need not be a multiple of
    the chunk: the tail is padded with positions that leave the state alone.
    Returns ``(o [T, H, d_v] float32, S_T float32)``."""
    T = q.shape[0]
    pad = -T % CHUNK
    n = (T + pad) // CHUNK

    def chunks(a):  # [T, H, ...] -> [n, H, CHUNK, ...], zero-padded
        a = jnp.pad(a.astype(_F32), [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        return jnp.moveaxis(a.reshape(n, CHUNK, *a.shape[1:]), 1, 2)

    S, o = jax.lax.scan(
        _chunk, S0.astype(_F32), tuple(chunks(a) for a in (q, k, v, g, beta))
    )
    H, d_v = o.shape[1], o.shape[3]  # o: [n, H, CHUNK, d_v]
    return jnp.moveaxis(o, 2, 1).reshape(n * CHUNK, H, d_v)[:T], S
