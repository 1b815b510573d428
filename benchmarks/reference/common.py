"""What both plain references share: weights from the seed, the lower-precision
control, and the one number they are compared by.

A reference is the published forward pass in straightforward ``jax.numpy``:
float32 activations, matrix multiplications at ``highest`` precision, no
kernels, no cache, no batching tricks. It takes its inputs from the seed and
nothing that the program made. Weights are made here, on the device, in the
type the configuration stores them in, and laid out in the pytree the
program's models read (stacked layers), so that one set of weights feeds the
reference and the program alike.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
INIT_STD = 0.02


def fold(seed: int) -> jax.Array:
    """A key from any whole number, also those past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def normal(key, std, shape, dtype):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def quantizer(quant):
    """``None`` leaves a matmul operand alone. ``"bf16"`` and ``"fp8"`` round
    it as a lower-precision path would: fp8 is e4m3 with one scale per tensor
    (the usual recipe), rounding straight through for gradients."""
    if quant is None:
        return lambda x: x
    if quant == "bf16":
        return lambda x: x.astype(jnp.bfloat16).astype(F32)
    if quant == "fp8":
        def q(x):
            scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
            y = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
            return x + jax.lax.stop_gradient(y - x)
        return q
    raise ValueError(f"unknown control precision {quant!r}")


def rel_err(got, want) -> float:
    """Global L2 distance over the L2 norm of the reference, over every leaf."""
    num = den = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = g.astype(F32), w.astype(F32)
        num += float(jnp.sum((g - w) ** 2))
        den += float(jnp.sum(w**2))
    return (num / den) ** 0.5
