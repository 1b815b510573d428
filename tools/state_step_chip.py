"""A slot's state stepped alone, on the chip: the kernel against the plain
step against the two-pass form.

    python tools/state_step_chip.py [--shapes kimi,solar,nemotron] [--block-bytes 1,2,4]

``ops.state_step`` at the shapes of the three cells that keep a state a slot
(`serve-batch-kimilinear`: 7 layers of 32 heads of 128 x 128 over 16 rows;
`serve-longdoc-solaropen2`: 3 layers of 64 heads of 128 x 128 over 32 rows;
`serve-chat-nemotron3super`: 5 layers of 128 heads of 64 x 128 over 64 rows,
``B`` and ``C`` by 8 groups). Every layer of a pool's state is stepped once a
call, each after the one before as in a model, the state donated, in three
forms:

- ``kernel``: :func:`ops.state_step.kda` / ``ssd`` on the rows where they lie;
- ``plain``: what ``paged.state_decode`` does elsewhere, ``kda_step`` /
  ``ssd_step`` on ``state[l, :rows]`` and the rows set back;
- ``two_pass``: the same mathematics in plain ``jax.numpy`` with both
  reductions taken from the state before the update (``S^T [e^g k, e^g q]``
  in one pass, ``o = S^T (e^g q) + u (k . q)``; for SSD ``y`` from the old
  ``h`` and the written term): two reads and a write, no kernel.

and under two ``keep``: every row live, and the share of rows the cell's
median step leaves as they were (none, 9 of 32, 1 of 64). A line a setting:
the microseconds a layer by the device trace and by the host's clock, the
share of 819 GB/s that is over one read and one write of every row's state,
and how far output and state lie from the plain form's. ``--block-bytes``
sweeps the kernel's block (MiB). Needs a TPU: a time from another backend
says nothing (PERF.md section 6, PR 49, holds the v5e's readings). The last
line of standard output is one JSON list.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks import trace_reduce  # noqa: E402
from ray_tpu.ops import state_step  # noqa: E402
from ray_tpu.ops.delta_rule import kda_step  # noqa: E402
from ray_tpu.ops.ssd import ssd_step  # noqa: E402

CALLS = 8
HBM_BYTES_A_US = 819e3  # a v5e's, as benchmarks/peaks.json has it
_PREC = jax.lax.Precision.HIGHEST
# layers, rows, heads, tile, groups of B and C (None: KDA), rows a median step keeps
SHAPES = {
    "kimi": (7, 16, 32, (128, 128), None, 0),
    "solar": (3, 32, 64, (128, 128), None, 9),
    "nemotron": (5, 64, 128, (64, 128), 8, 1),
}


def kda_two_pass(q, k, v, g, beta, S):
    """``kda_step`` with both reductions taken from ``S`` as it was."""
    decay = jnp.exp(g)
    both = jnp.einsum(
        "...kv,...ck->...cv", S, jnp.stack([decay * k, decay * q], axis=-2), precision=_PREC
    )
    u = beta[..., None] * (v - both[..., 0, :])
    o = both[..., 1, :] + u * jnp.sum(k * q, axis=-1, keepdims=True)
    return o, decay[..., None] * S + k[..., None] * u[..., None, :]


def ssd_two_pass(x, dt, A, B, C, D, h):
    """``ssd_step`` with ``y`` taken from ``h`` as it was and the written term."""
    H, G = x.shape[-2], B.shape[-2]
    heads = lambda a: jnp.repeat(a, H // G, axis=-2)  # noqa: E731
    decay, xdt = jnp.exp(dt * A), x * dt[..., None]
    seen = jnp.einsum("...hpn,...hn->...hp", h, heads(C), precision=_PREC)
    y = decay[..., None] * seen + xdt * heads(jnp.sum(B * C, axis=-1, keepdims=True)) + D[:, None] * x
    return y, decay[..., None, None] * h + xdt[..., None] * heads(B)[..., None, :]


def operands_of(key, rows, H, tile, groups):
    a, b = tile
    ks = jax.random.split(key, 6)
    if groups is None:
        l2 = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
        return (
            l2(jax.random.normal(ks[0], (rows, H, a))) * a**-0.5,
            l2(jax.random.normal(ks[1], (rows, H, a))),
            jax.random.normal(ks[2], (rows, H, b)),
            -jax.random.uniform(ks[3], (rows, H, a), minval=0.001, maxval=1.6),
            2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (rows, H))),
        )
    return (
        jax.random.normal(ks[0], (rows, H, a)),
        jax.nn.softplus(jax.random.normal(ks[1], (rows, H)) - 2.0),
        -jnp.exp(jax.random.normal(ks[2], (H,))),
        jax.random.normal(ks[3], (rows, groups, b)),
        jax.random.normal(ks[4], (rows, groups, b)),
        jax.random.normal(ks[5], (H,)),
    )


def program(form, layers, rows, groups):
    """Every layer stepped once: ``(state, operands, keep) -> (outs, state)``."""
    kernel, plain = (state_step.kda, kda_step) if groups is None else (state_step.ssd, ssd_step)
    two_pass = kda_two_pass if groups is None else ssd_two_pass

    def run(state, operands, keep):
        outs = []
        for l in range(layers):
            if form == "kernel":
                out, held = kernel(*operands, state_step.Rows(state, l, rows, keep))
                state = held.state
            else:
                state0 = state[l, :rows]
                out, state1 = (plain if form == "plain" else two_pass)(*operands, state0)
                state1 = jnp.where(keep[:, None, None, None], state0, state1)
                state = state.at[l, :rows].set(state1)
            out = jnp.where(keep[:, None, None], 0.0, out)
            outs.append(out)
            # As in a model, a layer's operands wait for the layer before: with
            # nothing to order a layer's last read of the state and the next
            # layer's write, the compiler copies the state a layer.
            wait = 0.0 * jnp.sum(out)
            operands = tuple(a + wait for a in operands)
        return jnp.stack(outs), state

    return jax.jit(run, donate_argnums=0)


def time_calls(run, state, operands, keep):
    """Host and device microseconds a call over ``CALLS`` calls, the state
    handed on from call to call as the engine hands its pool on."""
    outs, state = run(state, operands, keep)  # compiled, outside the timing
    jax.block_until_ready(state)
    log_dir = tempfile.mkdtemp(prefix="state_step_chip_")
    try:
        jax.profiler.start_trace(log_dir)
        t = time.perf_counter()
        for _ in range(CALLS):
            outs, state = run(state, operands, keep)
        jax.block_until_ready(state)
        host_us = (time.perf_counter() - t) / CALLS * 1e6
        jax.profiler.stop_trace()
        reduced = trace_reduce.reduce(
            trace_reduce.plain_from_xplane(trace_reduce.find_xplane(log_dir))
        )
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    ops = [[name, round(s / CALLS * 1e6, 1)] for name, s in reduced["ops"][:4]]
    return host_us, reduced["busy_s"] / CALLS * 1e6, ops


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES), help="comma-separated, of " + ", ".join(SHAPES))
    ap.add_argument("--forms", default="kernel,plain,two_pass")
    ap.add_argument("--block-bytes", default=None, help="comma-separated MiB a block of the kernel's to sweep")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU: the step's time is a device time")
    blocks = [int(float(m) * 2**20) for m in args.block_bytes.split(",")] if args.block_bytes else [
        state_step._BLOCK_BYTES
    ]
    out = []
    for name in args.shapes.split(","):
        layers, rows, H, tile, groups, kept = SHAPES[name]
        key = jax.random.key(H)
        operands = operands_of(key, rows, H, tile, groups)
        fresh = lambda: jax.random.normal(key, (layers, rows + 1, H, *tile), jnp.float32)  # noqa: E731
        bytes_a_layer = 2 * rows * H * tile[0] * tile[1] * 4
        for keeps in sorted({0, kept}):
            keep = jnp.arange(rows) * keeps % rows < keeps  # spread over the rows
            want_out, want_state = program("plain", layers, rows, groups)(fresh(), operands, keep)
            for form in args.forms.split(","):
                for block in blocks if form == "kernel" else blocks[:1]:
                    state_step._BLOCK_BYTES = block
                    jax.clear_caches()
                    run = program(form, layers, rows, groups)
                    got_out, got_state = run(fresh(), operands, keep)
                    diffs = (
                        float(jnp.max(jnp.abs(got_out - want_out))),
                        float(jnp.max(jnp.abs(got_state - want_state))),
                    )
                    del got_out, got_state
                    host_us, device_us, ops = time_calls(run, fresh(), operands, keep)
                    row = {
                        "shape": name, "form": form, "rows_kept": int(keep.sum()),
                        "block_mib": block / 2**20 if form == "kernel" else None,
                        "device_us_a_layer": round(device_us / layers, 1),
                        "host_us_a_layer": round(host_us / layers, 1),
                        "pct_of_bytes_speed": round(100 * bytes_a_layer / HBM_BYTES_A_US / (device_us / layers), 1),
                        "out_diff": diffs[0], "state_diff": diffs[1], "ops_us_a_call": ops,
                    }
                    print(json.dumps(row), file=sys.stderr, flush=True)
                    out.append(row)
            del want_out, want_state
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
