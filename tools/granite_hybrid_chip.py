"""Granite-4.0-H-Micro's two paged programs on the chip, scanned over the
layer pattern's period against the same forty layers walked one by one.

    python tools/granite_hybrid_chip.py [--forms scanned,unrolled] [--steps 30]

At the published widths and depth and the cell's cache (64 slots of 2,048
positions, 8,193 blocks of 16), for each form of ``granite_hybrid.paged_decode``
and of the 512-token ``paged_prefill`` (``unrolled=``): the seconds of a first
set-up (trace, lower and compile with an empty compile cache), of a warm one
(the same again after ``jax.clear_caches()``, the persistent cache holding the
programs: what a warm run's ``setup_s`` pays), and the milliseconds of a decode
step over 64 live slots at 300-700 positions and of a prefill of 500 tokens,
each timed to ``block_until_ready`` over ``--steps`` launches with the pool
donated, as the engine launches them. Also the deviation of the attention
layers' scores after the multiplier, over the keys a query sees (the draw of
``W_q`` and ``W_k`` aims at 1: ``granite_hybrid.qk_std``), measured on the
first attention layer's own normed input after a prefill. Needs a TPU. The last
line of standard output is one JSON object.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu.util.compile_cache import ensure_compile_cache  # noqa: E402

CACHE = ensure_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.models import granite_hybrid as gh, nemotron_h  # noqa: E402
from ray_tpu.models.common import _rms_norm  # noqa: E402

SLOTS, BLOCKS, BLOCK, SEQ, PREFILL = 64, 8193, 16, 2048, 512


def score_deviation(params, cfg, tokens) -> float:
    """The deviation of ``q . k x multiplier`` in the first attention layer,
    its input the hidden state the layers before it give ``tokens``."""
    place = cfg.period.index("attention")
    x = params["wte"].astype(cfg.dtype)[tokens] * jnp.asarray(cfg.embedding_multiplier, cfg.dtype)
    zero = jnp.zeros((cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state), jnp.float32)
    tail = jnp.zeros((cfg.conv_kernel - 1, cfg.conv_dim), cfg.dtype)
    r = cfg.residual_multiplier
    for p in (jax.tree.map(lambda a: a[0], q) for q in params["period"][:place]):
        out, _, _ = nemotron_h.mamba_prefill(_rms_norm(x, p["norm"], cfg.rms_eps), p, cfg, zero, tail, len(tokens))
        x = x + (r * out).astype(x.dtype)
        x = x + (r * gh.mlp(_rms_norm(x, p["mlp_norm"], cfg.rms_eps), p, cfg)).astype(x.dtype)
    p = jax.tree.map(lambda a: a[0], params["period"][place])
    q, k, _ = nemotron_h._qkv(_rms_norm(x, p["norm"], cfg.rms_eps), p, cfg)
    s = jnp.einsum("tkgd,skd->kgts", q, k).astype(jnp.float32) * cfg.attention_multiplier
    seen = jnp.tril(jnp.ones((len(tokens), len(tokens)), bool))
    return float(jnp.sqrt(jnp.sum(jnp.where(seen, s * s, 0)) / (jnp.sum(seen) * s.shape[0] * s.shape[1])))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--forms", default="scanned,unrolled")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--cpu-rehearsal", action="store_true", help="a tiny size on the CPU: debugs this script")
    args = ap.parse_args()
    if args.cpu_rehearsal:
        cfg = gh.GraniteHybridConfig.tiny(max_seq=SEQ, state_slots=SLOTS)
    elif jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU: the programs' times are device times")
    else:
        cfg = gh.GraniteHybridConfig(max_seq=SEQ, state_slots=SLOTS)
    rng = np.random.default_rng(0)
    params = gh.init_params(jax.random.key(0), cfg)
    pool = gh.init_pool(cfg, BLOCKS, BLOCK, SLOTS)
    W = SEQ // BLOCK
    tables = jnp.asarray(np.arange(1, SLOTS * W + 1).reshape(SLOTS, W), jnp.int32)
    positions = jnp.asarray(rng.integers(300, 700, SLOTS), jnp.int32)
    last = jnp.asarray(rng.integers(0, 256, SLOTS), jnp.int32)
    live = jnp.ones((SLOTS,), bool)
    toks = jnp.asarray(rng.integers(0, 256, (1, PREFILL)), jnp.int32)
    out = {"score_deviation": score_deviation(params, cfg, toks[0, :500]), "forms": {}}
    for form in args.forms.split(","):
        kw = {"unrolled": form == "unrolled"}
        decode = jax.jit(functools.partial(gh.paged_decode, cfg=cfg, block_size=BLOCK, **kw), donate_argnums=4)
        prefill = jax.jit(functools.partial(gh.paged_prefill, cfg=cfg, block_size=BLOCK, **kw), donate_argnums=5)
        d_args = lambda pool: (params, last, positions, tables, pool)  # noqa: E731
        p_args = lambda pool: (params, toks, jnp.int32(500), jnp.int32(0), tables[3], pool)  # noqa: E731
        row = {}
        for when in ("first", "warm"):
            if when == "first":
                shutil.rmtree(CACHE, ignore_errors=True)
                os.makedirs(CACHE, exist_ok=True)
            jax.clear_caches()
            t = time.perf_counter()
            dc = decode.lower(*d_args(pool), live=live).compile()
            row[f"{when}_setup_decode_s"] = round(time.perf_counter() - t, 2)
            t = time.perf_counter()
            pc = prefill.lower(*p_args(pool), slot=jnp.int32(3)).compile()
            row[f"{when}_setup_prefill512_s"] = round(time.perf_counter() - t, 2)
        pool, logits = dc(*d_args(pool), live=live)
        jax.block_until_ready(logits)
        t = time.perf_counter()
        for _ in range(args.steps):
            pool, logits = dc(*d_args(pool), live=live)
        jax.block_until_ready(logits)
        row["decode_step_ms"] = round((time.perf_counter() - t) / args.steps * 1e3, 3)
        pool, logits = pc(*p_args(pool), slot=jnp.int32(3))
        jax.block_until_ready(logits)
        t = time.perf_counter()
        for _ in range(args.steps):
            pool, logits = pc(*p_args(pool), slot=jnp.int32(3))
        jax.block_until_ready(logits)
        row["prefill512_ms"] = round((time.perf_counter() - t) / args.steps * 1e3, 3)
        row["mosaic_calls_decode"] = dc.as_text().count("tpu_custom_call")
        out["forms"][form] = row
        print(json.dumps({form: row}), file=sys.stderr, flush=True)
        del dc, pc
    stats = jax.devices()[0].memory_stats() or {}
    out["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
