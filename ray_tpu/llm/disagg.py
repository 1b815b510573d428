"""Disaggregated serving: prefill→decode KV handoff over the transfer fabric.

Reference parity: the prefill/decode disaggregation the reference serves
through vLLM's KV-transfer connectors (and the Gemma-on-TPU serving
comparison in PAPERS.md — the structural change that sets what TPU decode
should cost). A *prefill* replica runs a prompt through its engine once,
samples the first token, and ships the request's KV — at paged-pool BLOCK
granularity, straight off the device pool through the transfer fabric
(:mod:`ray_tpu.experimental.transfer`), no host staging on fabric
transports that support it — to the *decode* replica the router chose.
The decode replica scatters the pulled blocks into its own pool and joins
the request to its continuous-batching loop mid-decode: it never runs
whole-suffix prefill, so one long prompt can no longer stall a decode
batch anywhere in the decode tier.

Wire contract (the ``handoff`` dict the serve router carries between the
two hops):

    {"prompt":      [token ids],
     "first_token": int,            # sampled on the prefill replica
     "nblocks":     int,            # KV blocks covering [0, len(prompt))
     "block_size":  int,
     "kv":          arm descriptor  # transfer.fabric().arm() return
     "finished":    bool}           # stop/max_tokens hit at prefill:
                                    # no KV ships, decode short-circuits

Failure semantics: the pull is guarded by the seeded ``kvship`` fault
site (``RAY_TPU_FAULTS="…:kvship.sever"``) and by a broad except around
the real transfer — ANY failure frees the reservation and falls the
request back to local (chunked, when configured) prefill on the decode
replica. Greedy outputs are token-identical either way, so a severed
fabric degrades to round-12 behavior instead of hanging or diverging.

Armed exports that are never pulled (consumer died, sever) are released
after :data:`EXPORT_TTL_S` by the next export on the same engine, on top
of the fabric's own cap/TTL eviction.
"""

from __future__ import annotations

import functools
import time as _time

import jax
import jax.numpy as jnp

from ray_tpu.core.errors import PeerUnavailableError
from ray_tpu.util import flightrec as _flightrec
from ray_tpu.util import metrics as _metrics

_KV_SHIP_BYTES = _metrics.Counter(
    "raytpu_llm_kv_ship_bytes_total",
    "KV-cache bytes pulled replica-to-replica over the transfer fabric "
    "(disaggregated prefill->decode handoffs)",
)

# Prefill-side retention for armed-but-never-pulled exports: the consumer's
# pull normally lands within one router hop; after this long it certainly
# failed (sever, dead decode replica) and the staged copy is released.
EXPORT_TTL_S = 30.0


def _pad_pow2(n: int) -> int:
    """Block-count padding for the gather/scatter programs: one compile
    per power of two instead of one per distinct prompt length. Padded
    entries index the scratch block (id 0) — pulled bytes are bounded at
    2x and the decode-side scatter parks the padding in scratch, which is
    never read."""
    p = 1
    while p < n:
        p *= 2
    return p


@jax.jit
def _gather_blocks(pool, idx):
    """[2, L, nb, KH, bs, Dh] device copy of the pool rows at ``idx`` —
    the shippable view of one request's KV."""
    return jnp.stack([pool["k"][:, idx], pool["v"][:, idx]])


@functools.partial(jax.jit, donate_argnums=0)
def _scatter_blocks(pool, kv, idx):
    """Write a pulled KV block-stack into the pool rows at ``idx``, in
    place: the pool passed in is donated and the caller rebinds."""
    return {
        "k": pool["k"].at[:, idx].set(kv[0]),
        "v": pool["v"].at[:, idx].set(kv[1]),
    }


def export_kv(engine, req, first_token: int, finished: bool) -> dict:
    """Arm ``req``'s prompt KV for one remote pull and return the handoff
    descriptor. Called by the engine at the end of a prefill-only request,
    while the request still holds its blocks (the gather copies, so the
    blocks free immediately after)."""
    handoff = {
        "prompt": list(req.prompt),
        "first_token": int(first_token),
        "finished": bool(finished),
    }
    if finished:
        return handoff  # stop/max_tokens at prefill: nothing to ship
    from ray_tpu.experimental.transfer import fabric

    bs = engine._block_size
    T = len(req.prompt)
    nb = -(-T // bs)
    ids = list(req.blocks[:nb])
    ids += [0] * (_pad_pow2(nb) - nb)  # pad: scratch rows, ignored remotely
    t_x = _time.monotonic()
    kv = _gather_blocks(engine.pool, jnp.asarray(ids, jnp.int32))
    fab = fabric()
    desc = fab.arm(None, kv, (1,) * kv.ndim)
    handoff.update({"nblocks": nb, "block_size": bs, "kv": desc})
    if _flightrec.on():
        # Disagg leg 1 of 2: gather + arm on the prefill replica.
        _flightrec.record(
            "llm", "llm.kv_export", t=t_x,
            dur_s=_time.monotonic() - t_x,
            rid=req.request_id, nblocks=nb,
        )
    now = _time.monotonic()
    exports = engine._kv_exports
    exports.append((desc["uuid"], now))
    # Release exports past the TTL: their pull can no longer land (the
    # fabric's own cap/TTL eviction is the backstop for idle engines).
    while exports and now - exports[0][1] > EXPORT_TTL_S:
        uid, _t = exports.pop(0)
        fab.release_uuid(uid)
    return handoff


def pull_kv(handoff: dict, request_id: str = ""):
    """Pull one handoff's KV block-stack device-side. Raises on a severed
    transfer (injected via the seeded ``kvship`` site, or real) — the
    caller owns the local-prefill fallback."""
    from ray_tpu.core import faults

    inj = faults.active()
    if inj is not None:
        rule = inj.decide(
            "kvship", request_id, actions=frozenset({"sever", "delay"})
        )
        if rule is not None:
            if rule.action == "sever":
                raise PeerUnavailableError(
                    f"kv handoff severed mid-transfer (injected) for "
                    f"request {request_id!r}"
                )
            if rule.delay_s > 0:
                _time.sleep(min(rule.delay_s, 3600.0))
    from ray_tpu.experimental.transfer import fabric

    t_x = _time.monotonic()
    try:
        kv = fabric().pull(handoff["kv"])
    except Exception:
        # Disagg leg 2 of 2, failed pull: the caller's fallback takes
        # over; record the leg so the timeline shows WHERE the fabric
        # broke, then re-raise unchanged.
        if _flightrec.on():
            _flightrec.record(
                "llm", "llm.kv_pull", t=t_x,
                dur_s=_time.monotonic() - t_x, rid=request_id, ok=False,
            )
        raise
    if _flightrec.on():
        _flightrec.record(
            "llm", "llm.kv_pull", t=t_x,
            dur_s=_time.monotonic() - t_x, rid=request_id, ok=True,
        )
    if _metrics.metrics_enabled():
        _KV_SHIP_BYTES.inc(float(kv.size * kv.dtype.itemsize))
    return kv


def scatter_into_pool(engine, kv, block_ids: list):
    """Land a pulled block-stack in the engine's pool at ``block_ids``
    (padded rows go to scratch block 0 — written, never read)."""
    nb = len(block_ids)
    pad = kv.shape[2] - nb
    ids = list(block_ids) + [0] * pad
    return _scatter_blocks(engine.pool, kv, jnp.asarray(ids, jnp.int32))
