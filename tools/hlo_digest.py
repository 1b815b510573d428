"""Digests of the paged programs as they are handed to the compiler, to show
that a change left another family's programs alone (PERF.md section 6, "PR 55
was refused ..."): run it from the root of two checkouts and ``diff`` the two
outputs.

    cd <checkout> && python <this file> [family ...] > digests.txt

One line a (family, program) at the family's tiny size, lowered on the CPU
through ``paged.paged_prefill`` / ``paged.paged_decode``: the sha256 of the
StableHLO text and of the same text with every operation's location (file,
function, line and column, the checkout's path cut off), which is what the
compile cache keys on beside the instructions. A family the checkout does not
have prints ``absent``.
"""

from __future__ import annotations

import hashlib
import importlib
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import paged  # noqa: E402


def lower(cfg, mod, program: str):
    bs, width, slots = 16, 8, 4
    shapes = jax.eval_shape(
        lambda key: (mod.init_params(key, cfg), paged.init_block_pool(cfg, width * slots + 1, bs, slots)),
        jax.random.PRNGKey(0),
    )
    table = jnp.arange(1, width + 1, dtype=jnp.int32)
    if len(paged.cache(cfg).retention) > 1:  # a block table a layer kind
        table = jnp.stack([table, table])
    if program == "prefill":
        def run(params, pool, tokens):
            return paged.paged_prefill(
                params, tokens, jnp.int32(20), jnp.int32(0), table, pool, cfg, block_size=bs, slot=jnp.int32(1)
            )
        operand = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    else:
        def run(params, pool, tokens):
            return paged.paged_decode(
                params, tokens, jnp.full(slots, 5, jnp.int32), jnp.stack([table] * slots), pool, cfg,
                block_size=bs, live=jnp.ones(slots, bool),
            )
        operand = jax.ShapeDtypeStruct((slots,), jnp.int32)
    return jax.jit(run).lower(*shapes, operand)


def main() -> int:
    sha = lambda text: hashlib.sha256(text.encode()).hexdigest()[:16]  # noqa: E731
    here = os.getcwd() + os.sep
    for name in sys.argv[1:] or list(paged._FAMILIES):
        try:
            mod = importlib.import_module(paged._FAMILIES[name])
        except (KeyError, ImportError):
            print(f"{name} absent")
            continue
        cfg = next(
            v for v in vars(mod).values()
            if isinstance(v, type) and getattr(v, "family", None) == name and hasattr(v, "tiny")
        ).tiny()
        for program in ("prefill", "decode"):
            lowered = lower(cfg, mod, program)
            located = lowered.as_text(debug_info=True).replace(here, "")
            print(f"{name} {program} instructions {sha(lowered.as_text())} with_locations {sha(located)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
