"""KDA, the linear-attention mixer of ``kimi_linear`` and ``solar_open2``: a
gated delta rule with a decay per key channel (:mod:`ray_tpu.ops.delta_rule`)
behind short causal convolutions over ``q``, ``k`` and ``v``, an RMSNorm per
head and a sigmoid output gate. One implementation, in the two forms serving
needs (:func:`kda_prefill`, :func:`kda_decode`), and the layer's random weights
(:func:`draw_kda`). A configuration gives ``d_model``, ``kda_heads``,
``kda_head_dim``, ``kda_gate_rank``, ``conv_kernel``, ``conv_dim``,
``kda_neg_eigval``, ``rms_eps``, ``dtype`` and ``param_dtype``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.models.common import _rms_norm, stage
from ray_tpu.ops import delta_scan, state_step

Params = dict
_F32 = jnp.float32


def draw_kda(w, keys, cfg, resid: float) -> Params:
    """A KDA layer's random weights: ``w(shape, std)`` draws a matrix in the
    parameter dtype and ``keys`` yields a key a draw, both the caller's, so
    that a family's weights come off one stream in one order; ``resid`` is the
    deviation of the projection back to the residual stream. ``A_log`` and
    ``dt_bias`` as the published modelling code draws them (A in [1, 16], a
    time step in [0.001, 0.1])."""
    D, H, dk, r = cfg.d_model, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_gate_rank
    dt = jnp.exp(jax.random.uniform(
        next(keys), (H * dk,), _F32, jnp.log(0.001), jnp.log(0.1)))
    return {
        "wqkv": w((D, cfg.conv_dim)),
        "conv": w((cfg.conv_kernel, cfg.conv_dim), cfg.conv_kernel**-0.5),
        "f_down": w((D, r)), "f_up": w((r, H * dk)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "A_log": jnp.log(jax.random.uniform(next(keys), (H,), _F32, 1.0, 16.0)),
        "wb": w((D, H)),
        "g_down": w((D, r)), "g_up": w((r, H * dk)),
        "o_norm": jnp.ones((dk,), cfg.param_dtype),
        "wo": w((H * dk, D), resid),
    }


def _kda_inputs(h, mixed, p, cfg):
    """From the normed input ``h`` [..., D] and the convolved, SiLU'd
    projections ``mixed`` [..., 3 H d]: ``(q, k, v, g, beta)`` with heads
    split out, ``q`` and ``k`` normalised, ``g`` the log decay. ``beta`` is a
    sigmoid, in (0, 1), and twice that, in (0, 2), where the configuration
    allows the transition ``I - beta k k^T`` a negative eigenvalue along ``k``
    (``kda_neg_eigval``: the published ``kda_allow_neg_eigval``)."""
    H, d = cfg.kda_heads, cfg.kda_head_dim
    dt = cfg.dtype
    q, k, v = (
        a.reshape(*a.shape[:-1], H, d).astype(_F32)
        for a in jnp.split(mixed, 3, axis=-1)
    )
    l2 = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    f = (h @ p["f_down"].astype(dt)) @ p["f_up"].astype(dt)
    g = -jnp.exp(p["A_log"].astype(_F32))[:, None] * jax.nn.softplus(
        (f.astype(_F32) + p["dt_bias"].astype(_F32)).reshape(*f.shape[:-1], H, d)
    )
    beta = jax.nn.sigmoid((h @ p["wb"].astype(dt)).astype(_F32))
    if cfg.kda_neg_eigval:
        beta = 2.0 * beta
    return l2(q) * d**-0.5, l2(k), v, g, beta


@stage("state_out")
def _kda_output(h, o, p, cfg):
    """RMSNorm per head, the sigmoid output gate, ``W_o``."""
    dt = cfg.dtype
    gate = (h @ p["g_down"].astype(dt)) @ p["g_up"].astype(dt)
    o = _rms_norm(o, p["o_norm"].astype(_F32), cfg.rms_eps)  # o is float32
    o = o.reshape(*o.shape[:-2], -1) * jax.nn.sigmoid(gate.astype(_F32))
    return o.astype(dt) @ p["wo"].astype(dt)


def kda_prefill(h, p, cfg, S0, tail, length):
    """``h`` [T, D] normed, of which the first ``length`` rows are tokens;
    ``S0`` [H, d_k, d_v] (or, where the scan's kernel runs, a
    :class:`ray_tpu.ops.delta_scan.Held` of it) and ``tail`` [K-1, 3 H d] are
    the state and the last pre-convolution rows before row 0 (zeros at the
    start of a sequence).
    Returns ``(out [T, D], S, tail)`` as of row ``length``: padded rows do not
    touch the state."""
    T, K = h.shape[0], cfg.conv_kernel
    dt = cfg.dtype
    with stage("state_in"):
        x = jnp.concatenate([tail.astype(dt), h @ p["wqkv"].astype(dt)])  # [K-1+T, C]
        conv = p["conv"].astype(dt)
        mixed = sum(conv[j] * x[j : j + T] for j in range(K))
        q, k, v, g, beta = _kda_inputs(h, jax.nn.silu(mixed), p, cfg)
        live = (jnp.arange(T) < length)[:, None]
        g, beta = g * live[..., None], beta * live
    with stage("state_scan"):
        o, S = delta_scan.kda(q, k, v, g, beta, S0)
        tail = jax.lax.dynamic_slice_in_dim(x, length, K - 1, axis=0)
    return _kda_output(h, o, p, cfg), S, tail


def kda_decode(h, p, cfg, S, tail):
    """One token a row: ``h`` [B, D], ``S`` [B, H, d_k, d_v] (or the rows
    where they lie, a :class:`ray_tpu.ops.state_step.Rows`), ``tail`` [B,
    K-1, 3 H d]. Returns ``(out [B, D], S, tail)``."""
    dt = cfg.dtype
    with stage("state_in"):
        x = jnp.concatenate([tail.astype(dt), (h @ p["wqkv"].astype(dt))[:, None]], axis=1)
        mixed = jnp.einsum("kc,bkc->bc", p["conv"].astype(dt), x)
        q, k, v, g, beta = _kda_inputs(h, jax.nn.silu(mixed), p, cfg)
    with stage("state_scan"):
        o, S = state_step.kda(q, k, v, g, beta, S)
    out = _kda_output(h, o, p, cfg)
    with stage("state_scan"):
        return out, S, x[:, 1:]
