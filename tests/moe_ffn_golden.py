"""What ``latent_moe.moe_ffn`` gave, before it learnt a latent and experts
without gates (commit 5c286f4), for the two families that called it then:
stored outputs (``moe_ffn_golden.npz``) for a seeded layer and seeded rows.
``test_kimi_linear.py`` and ``test_mla_moe.py`` hold today's function to them.
The weights come straight from ``jax.random`` (the same bits everywhere) and
not through a family's ``init_params``, so only ``moe_ffn`` and ``route`` can
move these numbers. They were bit for bit what PR 35's function gave; the
tolerance is what a float32 sum in another order keeps, so a kernel in the
grouped products' place can stay inside it."""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import moe_gmm

ROWS, REAL = 40, 37  # rows of input, of which the first REAL are tokens


def take_arm(monkeypatch, arm: str):
    """Send ``moe_ffn``'s grouped products through ``arm``: ``ragged_dot`` is
    what a CPU takes by itself; ``kernel`` patches the predicate and runs
    ``ops.moe_gmm.gmm`` in the Pallas interpreter, at whatever widths the
    layer has (no whole lane tiles: one column tile)."""
    if arm == "kernel":
        monkeypatch.setattr(moe_gmm, "fits", lambda *a, **kw: True)
        monkeypatch.setattr(moe_gmm, "gmm", functools.partial(moe_gmm.gmm, interpret=True))


def case(cfg, offset: int, held: int, bias: bool):
    """``(h, the layer's parameters, cfg cut to the share, valid)``: a SwiGLU
    expert layer of ``cfg``'s sizes holding experts ``offset`` to ``offset +
    held``, with a selection bias if the family has one."""
    D, F, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    Fs = F * cfg.n_shared_experts
    shapes = {
        "router": (D, E), "e_gate": (held, D, F), "e_up": (held, D, F), "e_down": (held, F, D),
        "s_gate": (D, Fs), "s_up": (D, Fs), "s_down": (Fs, D),
    }
    keys = jax.random.split(jax.random.key(14), len(shapes) + 2)
    p = {n: 0.05 * jax.random.normal(k, s) for (n, s), k in zip(shapes.items(), keys)}
    p["router"] = p["router"] * (D**-0.5 / 0.05)
    if bias:
        p["router_bias"] = 0.1 * jax.random.normal(keys[-2], (E,))
    h = jax.random.normal(keys[-1], (ROWS, D))
    share = dataclasses.replace(cfg, experts_held=held, expert_offset=offset)
    return h, p, share, jnp.arange(ROWS) < REAL


def assert_as_before(name: str, got):
    """``got`` = ``moe_ffn``'s ``(y, counts, picks)`` against the stored case."""
    with np.load(os.path.join(os.path.dirname(__file__), "moe_ffn_golden.npz")) as was:
        y, counts, picks = got
        np.testing.assert_array_equal(picks, was[f"{name}.picks"])
        np.testing.assert_array_equal(counts, was[f"{name}.counts"])
        np.testing.assert_allclose(y, was[f"{name}.y"], rtol=1e-4, atol=1e-7)
