"""Speculative decoding on the decode tier: draft-propose, target-verify.

Reference parity: the draft/target speculative scheme vLLM supplies under
ray.llm (and the Gemma-on-TPU serving playbook in PAPERS.md). A small
draft model proposes ``k`` greedy tokens per engine step; the target model
scores the carried last token plus all ``k`` proposals in ONE multi-token
forward (:func:`ray_tpu.models.paged.paged_verify`) and accepts the
longest matching prefix plus one corrected token — each step yields 1..k+1
tokens at one target forward. **Greedy verification is token-identical to
vanilla decode by construction** (CI-pinned): every accepted token is
exactly the argmax the vanilla loop would have produced in sequence.

The draft **shares the paged pool's structure**: one BlockManager, one
block-table array — the draft KV is a parallel ``{"k","v"}`` pytree
indexed by the same physical block ids, sized by the draft config's own
layer/head dims. Prefix-shared blocks hold the same draft KV whoever
wrote them (same tokens x same draft params), so refcounted sharing stays
sound without any extra bookkeeping.

Engine contract (enforced by ``LLMEngine.step``):

- a spec step runs only when EVERY active slot is greedy (temperature 0),
  has draft KV (``spec_ready``), and sits ``k`` tokens clear of
  ``max_seq``; any other step falls back to the vanilla one-token program
  — token-identical either way, so eligibility is a scheduling choice,
  never a correctness one.
- rejected draft positions leave stale KV in both pools. Safe: the next
  consume at those positions scatters BEFORE the gather (the same
  invariant chunked prefill relies on), and unconsumed positions are
  masked (``col <= position``).

``RAY_TPU_SPEC_DECODE=0`` is the kill switch: the engine never builds a
draft model and every step is the vanilla path — byte-identical to the
round-12 engine.
"""

from __future__ import annotations

import dataclasses
import functools
import time as _time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import paged
from ray_tpu.util import metrics as _metrics

# Telemetry rides the engine histograms/counters (ITL is observed by the
# engine per accepted token); these series are the speculation-specific
# view: proposal volume, acceptance, and the resulting rate.
_SPEC_DRAFTED = _metrics.Counter(
    "raytpu_llm_spec_drafted_total",
    "draft tokens proposed AND eligible for acceptance (the per-slot k is "
    "budget-clamped: a request one token from max_tokens can accept no "
    "drafts, so its step contributes none — keeping accept_rate a pure "
    "draft-quality signal). Draft-model cost is spec step count x k.",
)
_SPEC_ACCEPTED = _metrics.Counter(
    "raytpu_llm_spec_accepted_total",
    "draft tokens accepted by target verification (rate of this over "
    "drafted = the accept rate)",
)
_SPEC_ACCEPT_RATE = _metrics.Gauge(
    "raytpu_llm_spec_accept_rate",
    "cumulative fraction of drafted tokens the target model accepted",
    tag_keys=("replica",),  # gauge: untagged would last-wins across replicas
)


class SpecDecoder:
    """Draft model + verification programs bolted onto one LLMEngine.

    Owns the draft params and the draft KV (a block-id-parallel pool) and
    runs the propose→verify→accept cycle of one engine step. The engine
    decides WHEN a spec step is legal; this class only executes it.
    """

    def __init__(self, engine, draft_cfg, k: int):
        if k < 1:
            raise ValueError(f"spec_decode_tokens must be >= 1, got {k}")
        target_cfg = engine.model_config
        if draft_cfg is None:
            raise ValueError(
                "spec_decode_tokens > 0 requires draft_model_config "
                "(a small model of the same families as model_config)"
            )
        if draft_cfg.vocab_size != target_cfg.vocab_size:
            raise ValueError(
                f"draft vocab ({draft_cfg.vocab_size}) must equal the "
                f"target vocab ({target_cfg.vocab_size}): proposals are "
                f"target token ids"
            )
        self.engine = engine
        self.k = int(k)
        # The draft's positional tables must cover the serving window.
        if getattr(draft_cfg, "max_seq", 0) < engine.config.max_seq:
            draft_cfg = dataclasses.replace(
                draft_cfg, max_seq=engine.config.max_seq
            )
        self.cfg = draft_cfg
        self._model = paged.family(draft_cfg)
        if engine.config.draft_weights_path:
            # Trained/distilled draft checkpoint (same pickled-pytree
            # contract as LLMConfig.weights_path for the target): the
            # accept-rate gauge only means anything with one of these —
            # a random-init draft agrees with the target by chance.
            import pickle

            with open(engine.config.draft_weights_path, "rb") as f:
                self.params = jax.tree.map(jnp.asarray, pickle.load(f))
        else:
            self.params = self._model.init_params(
                jax.random.key(engine.config.seed), draft_cfg
            )
        bs = engine._block_size
        self.pool = paged.init_block_pool(
            draft_cfg, engine.block_mgr.num_blocks, bs
        )
        # The pools (the draft's own, and the engine's through
        # _verify) are donated, as the engine donates its own: each
        # call below rebinds the pool it passed in.
        self._d_prefill = jax.jit(
            functools.partial(
                paged.paged_prefill, cfg=draft_cfg, block_size=bs
            ),
            donate_argnums=5,
        )
        self._d_decode = jax.jit(
            functools.partial(
                paged.paged_decode, cfg=draft_cfg, block_size=bs
            ),
            donate_argnums=4,
        )
        self._verify = jax.jit(
            functools.partial(
                paged.paged_verify, cfg=target_cfg, block_size=bs
            ),
            donate_argnums=4,
        )

    def prefill_draft(self, req) -> bool:
        """Run the draft model over ``req``'s WHOLE prompt so its KV covers
        [0, T) — called once, at the moment the request joins the decode
        batch (the draft has no prefix pool: it re-prefills shared
        prefixes, writing the identical values). Returns False when no
        prefill bucket fits inside max_seq (the request then simply never
        speculates)."""
        eng = self.engine
        T = len(req.prompt)
        bucket = next(
            (
                b
                for b in eng.config.prefill_buckets
                if b >= T and b <= eng.config.max_seq
            ),
            None,
        )
        if bucket is None:
            return False
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :T] = req.prompt
        self.pool, _ = self._d_prefill(
            self.params,
            jnp.asarray(toks),
            jnp.asarray(T, jnp.int32),
            jnp.asarray(0, jnp.int32),
            jnp.asarray(eng.block_tables[req.slot]),
            self.pool,
        )
        return True

    # -- the spec step --------------------------------------------------------

    def step(self, active: list) -> list:
        """One propose→verify→accept cycle for the whole decode batch.
        Mutates the engine's pool/positions/last_tokens exactly as a
        run of vanilla steps would; returns the requests that finished."""
        eng = self.engine
        k = self.k
        instrument = _metrics.metrics_enabled()
        last = jnp.asarray(eng.last_tokens)
        pos = jnp.asarray(eng.positions)
        tables = jnp.asarray(eng.block_tables)
        # 1) Draft proposes k tokens autoregressively. The chain stays
        # device-resident (each proposal feeds the next draft decode as a
        # jax array); only the final [B, k+1] token block and the verify
        # argmax come back to the host.
        proposals = []
        dlast, dpos = last, pos
        for _ in range(k):
            self.pool, dlogits = self._d_decode(
                self.params, dlast, dpos, tables, self.pool
            )
            dlast = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
            proposals.append(dlast)
            dpos = dpos + 1
        tokens = jnp.concatenate(
            [last[:, None]] + [p[:, None] for p in proposals], axis=1
        )  # [B, k+1]
        # 2) Target verifies all k+1 tokens in one forward.
        eng.pool, logits = self._verify(
            eng.params, tokens, pos, tables, eng.pool
        )
        greedy = np.asarray(jnp.argmax(logits, axis=-1))  # raylint: disable=RL101 -- the spec step's intended sync: verify argmax readback feeding host-side acceptance
        prop = np.asarray(tokens)[:, 1:]  # raylint: disable=RL101 -- proposal readback paired with the verify argmax (host-side accept loop)
        # 3) Host-side acceptance per active slot: longest matching draft
        # prefix + the corrected/bonus token, clamped to the request's
        # remaining budget; stop tokens truncate the burst.
        now = _time.perf_counter()
        finished = []
        drafted = accepted = 0
        from ray_tpu.llm.engine import _ITL_SECONDS

        for req in active:
            b = req.slot
            d = 0
            while d < k and prop[b, d] == greedy[b, d]:
                d += 1
            remaining = req.max_tokens - len(req.generated)
            n = min(d + 1, remaining)
            applied = 0
            for i in range(n):
                tok = int(greedy[b, i])
                req.generated.append(tok)
                applied += 1
                if instrument and (req.t_last_token or i):
                    # Burst semantics: the first token pays the step gap,
                    # the rest land with it (that IS the client-visible
                    # inter-token latency of an accepted burst).
                    _ITL_SECONDS.observe(
                        (now - req.t_last_token) if i == 0 else 0.0
                    )
                if (
                    tok == req.stop_token
                    or len(req.generated) >= req.max_tokens
                ):
                    break
            req.t_last_token = now
            eng.stats["tokens_generated"] += applied
            # Accept-rate denominator: only drafts the budget could have
            # accepted (a perfect draft scores 1.0 regardless of where
            # max_tokens falls in the burst).
            drafted += min(k, max(0, remaining - 1))
            accepted += max(0, applied - 1)
            eng.positions[b] += applied
            eng.last_tokens[b] = req.generated[-1]
            eng._maybe_finish(req)
            if req.finished:
                finished.append(req)
        eng.stats["spec_steps"] += 1
        eng.stats["spec_drafted"] += drafted
        eng.stats["spec_accepted"] += accepted
        if instrument:
            from ray_tpu.llm.engine import _replica_tags

            _SPEC_DRAFTED.inc(float(drafted))
            if accepted:
                _SPEC_ACCEPTED.inc(float(accepted))
            total = eng.stats["spec_drafted"]
            if total:
                _SPEC_ACCEPT_RATE.set(
                    eng.stats["spec_accepted"] / total, _replica_tags()
                )
        return finished

    def accept_rate(self) -> float:
        total = self.engine.stats["spec_drafted"]
        return (self.engine.stats["spec_accepted"] / total) if total else 0.0
