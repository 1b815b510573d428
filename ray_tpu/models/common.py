"""Shared model-family infrastructure: GPipe pipelining + chunked LM loss.

Used by every decoder family (:mod:`ray_tpu.models.gpt2`,
:mod:`ray_tpu.models.llama`): the stacked-layers GPipe schedule over a
``pp`` mesh axis and the sequence-chunked, rematerialized LM-head loss.
Contracts are family-neutral:

- ``pipelined_blocks(blocks, x, block_fn, mesh, n_micro)`` — ``block_fn``
  is any ``(x, layer_params) -> (x, aux_scalar)``.
- ``chunked_lm_loss(x, head, targets, chunk)`` — ``head`` is the OUTPUT
  projection oriented ``[V, D]`` (contract over D); tied-embedding models
  pass their wte directly, untied ones pass ``lm_head.T`` (a transpose
  under jit fuses into the matmul).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

# The stages of a device program, one vocabulary for every family's prefill and
# decode programs and for the train step: what a reader of a device trace
# (benchmarks/stage_time.py) files an operation's time under (PERF.md section 3).
STAGES = (
    "attn_proj",  # q/k/v/gate projections, head norms, rotation, MLA's low-rank pairs and absorbs, W_o
    "attn_select",  # an indexer in front of attention: its projections, rotation, scores and top-k
    "attn_core",  # scores and the weighted sum over the table: kernel, fold or gather, the sink
    "pool_write",  # rows or blocks of keys, values or latent rows into the pool
    "state_in",  # a recurrent mixer up to its scan: in-projection, convolution, forming q,k,v,g,beta / B,C,dt
    "state_scan",  # the chunked scan or the state step, the slot's state and tail in and out
    "state_out",  # norm, gate and out-projection behind the scan
    "router",  # of an expert layer, all that is no grouped product: scores, top-k, sort, un-sort, combine
    "experts",  # grouped products, activation, latent in/out, the shared expert
    "mlp",  # a dense feed-forward
    "embed_head",  # embedding gather, final norm, logits, argmax, the packed counters, the loss
    "optimizer",  # the train step's update
)
STAGE_PREFIX = "st."


class stage(contextlib.ContextDecorator):
    """``jax.named_scope`` of one of :data:`STAGES`, as a context manager or
    a decorator: every operation traced under it carries ``st.<name>`` in the
    ``op_name`` of its HLO metadata (``jit(paged_prefill)/.../st.mlp/dot_general``,
    and ``transpose(jvp(st.mlp))`` in a backward pass), which reaches the
    device trace. Metadata only: no instruction of a compiled program changes.
    A layer norm goes with the stage it feeds, a residual add with the stage
    that made the branch. Stages are not nested: an operation carries one.

    A decorated function opens a scope of its own a call (jax's scope object
    keeps the name stack it replaced on itself, so one object shared by every
    call would be neither re-entrant nor safe under two tracing threads)."""

    def __init__(self, name: str):
        if name not in STAGES:
            raise ValueError(f"{name!r} is no stage of the vocabulary {STAGES}")
        self.name = name

    def _recreate_cm(self):
        return jax.named_scope(STAGE_PREFIX + self.name)

    def __enter__(self):
        self._scope = self._recreate_cm()
        return self._scope.__enter__()

    def __exit__(self, *exc):
        return self._scope.__exit__(*exc)


def _rms_norm(x, scale, eps):
    """RMSNorm in float32, back in ``x``'s dtype: every family's but GPT-2's."""
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms * scale).astype(x.dtype)


def chunked_lm_loss(
    x: jax.Array, wte: jax.Array, targets: jax.Array, chunk: int
) -> jax.Array:
    """Sum of next-token cross-entropies, scanning over SEQUENCE chunks.

    Each chunk's logits ([B, chunk, vocab], f32-accumulated on the MXU) live
    only inside the scan body and are rematerialized in backward
    (jax.checkpoint), so nothing O(B*S*vocab) is ever resident in HBM — the
    checkpointed scan trades one extra lm-head matmul per chunk for ~6.6 GB
    of logits+grad at B=32. Chunking runs along S (not the flattened token
    dim) so the dp/fsdp-sharded batch dim stays intact under SPMD.
    Padded positions carry target -1 and contribute zero.
    """
    B, S, D = x.shape
    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=-1)
    xs = x.reshape(B, n_chunks, chunk, D).transpose(1, 0, 2, 3)
    ts = targets.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

    def chunk_loss(total, xs_t):
        x_c, t_c = xs_t  # [B, chunk, D], [B, chunk]
        logits = jax.lax.dot_general(
            x_c, wte, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [B, chunk, vocab] f32
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(t_c, 0)[..., None], axis=-1
        )[..., 0]
        ce = jnp.where(t_c >= 0, lse - tgt, 0.0)
        return total + jnp.sum(ce), None

    total, _ = jax.lax.scan(
        jax.checkpoint(chunk_loss), jnp.zeros((), jnp.float32), (xs, ts)
    )
    return total



def pipelined_blocks(blocks, x, block_fn, mesh, *, n_micro):
    """GPipe over the mesh's `pp` axis: each stage holds L/pp stacked
    layers; microbatches of activations rotate stage-to-stage via ppermute
    inside a scan (scaling-book pipelining recipe — compiled collectives,
    no per-hop host involvement). Differentiable: autodiff reverses the
    schedule through scan+ppermute.

    Only `pp` is manual inside the shard_map (`axis_names={"pp"}`); batch /
    tensor / sequence axes stay under the compiler's automatic SPMD."""
    from jax.sharding import PartitionSpec as P

    B = x.shape[0]
    if B % n_micro:
        raise ValueError(
            f"batch {B} not divisible by pipeline_microbatches {n_micro}"
        )
    n_layer = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    if n_layer % mesh.shape["pp"]:
        raise ValueError(
            f"n_layer {n_layer} not divisible by the {mesh.shape['pp']} "
            f"pipeline stages (pp mesh axis)"
        )

    def run_stage(blocks_local, x_mb):
        out, aux_layers = jax.lax.scan(block_fn, x_mb, blocks_local)
        return out, jnp.sum(aux_layers)

    pp = mesh.shape["pp"]

    orig_dtype = x.dtype
    # f32 at the shard_map boundary ONLY on CPU: the replicated input's
    # BACKWARD is a psum over pp, and a bf16 all-reduce trips XLA:CPU's
    # AllReducePromotion pass (crash). TPUs keep the bf16 boundary — f32
    # there would double collective traffic for nothing.
    boundary_dtype = (
        jnp.float32 if jax.default_backend() == "cpu" else orig_dtype
    )

    def pipelined(blocks_local, x_full_b):
        x_full = x_full_b.astype(orig_dtype)
        idx = jax.lax.axis_index("pp")
        mb = B // n_micro
        xs = x_full.reshape(n_micro, mb, *x_full.shape[1:])
        n_steps = n_micro + pp - 1
        perm = [(i, (i + 1) % pp) for i in range(pp)]

        def step(carry, t):
            recv, outs, aux = carry
            # Stage 0 feeds microbatch t (clamped; late steps are bubble).
            feed = xs[jnp.minimum(t, n_micro - 1)]
            inp = jnp.where(idx == 0, feed, recv)
            out, aux_mb = run_stage(blocks_local, inp)
            # Aux counts only GENUINE microbatch steps for this stage
            # (stage s holds microbatch t-s at step t); bubble steps
            # process clamped duplicates and must not contribute.
            genuine = jnp.logical_and(t >= idx, t < idx + n_micro)
            aux = aux + jnp.where(genuine, aux_mb, 0.0)
            # The LAST stage completes microbatch t-(pp-1) at step t.
            mo = jnp.clip(t - (pp - 1), 0, n_micro - 1)
            take = jnp.logical_and(idx == pp - 1, t >= pp - 1)
            outs = outs.at[mo].set(jnp.where(take, out, outs[mo]))
            return (jax.lax.ppermute(out, "pp", perm), outs, aux), None

        # Carries become device-varying over pp after the first ppermute;
        # mark the (replicated-zero) initial values accordingly.
        init = jax.tree.map(
            lambda z: jax.lax.pcast(z, ("pp",), to="varying"),
            (
                jnp.zeros_like(xs[0]),
                jnp.zeros_like(xs),
                jnp.zeros((), jnp.float32),
            ),
        )
        (_, outs, aux), _ = jax.lax.scan(step, init, jnp.arange(n_steps))
        # Valid only on the last stage; broadcast to every pp rank (the lm
        # head and loss are replicated over pp).
        outs = jax.lax.psum(
            jnp.where(idx == pp - 1, outs, 0.0).astype(boundary_dtype),
            "pp",
        ).astype(x_full.dtype)
        # Per-stage aux sums over this stage's layers; per-microbatch means
        # average to the full-batch mean (equal microbatch sizes), so
        # psum(stage sums)/n_micro == the unpipelined layer sum.
        aux = jax.lax.psum(aux, "pp") / n_micro
        return outs.reshape(B, *x_full.shape[1:]), aux

    layer_specs = jax.tree.map(lambda _: P("pp"), blocks)
    return jax.shard_map(  # raylint: disable=RL102 -- constructed under the enclosing jit trace of the model fwd; rebuilt once per outer trace, not per step
        pipelined,
        mesh=mesh,
        in_specs=(layer_specs, P()),
        out_specs=(P(), P()),
        axis_names={"pp"},
    )(blocks, x.astype(boundary_dtype))

