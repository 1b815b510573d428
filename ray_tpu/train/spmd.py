"""Generic sharded train-step construction.

The recipe (scaling-book style): pick a mesh, place params with NamedShardings
derived from logical rules, jit the step with donated state, and let XLA
insert the collectives. There is no hand-written gradient all-reduce anywhere —
sharding propagation + `with_sharding_constraint` pin the few places XLA needs
a hint. This replaces the reference's per-backend trainer plumbing
(torch DDP setup in python/ray/train/torch/config.py, gradient averaging via
NCCL) with compiled SPMD.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.common import stage

# TrainState is a plain pytree dict: {"params", "opt_state", "step"} —
# checkpointable with orbax, shardable leaf-by-leaf, no framework classes.
TrainState = dict


def make_train_state(
    init_params_fn: Callable[[jax.Array], Any],
    optimizer: optax.GradientTransformation,
    rng: jax.Array,
    *,
    param_shardings: Any | None = None,
) -> TrainState:
    """Initialize params (sharded at creation — no host-side giant arrays) and
    optimizer state (inherits param shardings via XLA propagation)."""
    if param_shardings is not None:
        params = jax.jit(init_params_fn, out_shardings=param_shardings)(rng)  # raylint: disable=RL102 -- one-shot jit at state construction (trainer build); per-build retrace is the point -- fresh shapes/shardings
    else:
        params = jax.jit(init_params_fn)(rng)  # raylint: disable=RL102 -- one-shot jit at state construction (trainer build); per-build retrace is the point -- fresh shapes/shardings
    opt_state = jax.jit(optimizer.init)(params)  # raylint: disable=RL102 -- one-shot jit at optimizer-state init (trainer build), traced once per build
    return {"params": params, "opt_state": opt_state, "step": jnp.zeros((), jnp.int32)}


def state_shardings(state: TrainState) -> Any:
    """Extract the NamedSharding tree of a live TrainState (for checkpoint
    restore onto the same mesh)."""
    return jax.tree.map(lambda x: x.sharding, state)


def make_train_step(
    loss_fn: Callable[[Any, Any], tuple[jax.Array, dict]],
    optimizer: optax.GradientTransformation,
    *,
    mesh: Mesh | None = None,
    batch_spec: P | None = None,
    param_shardings: Any | None = None,
    donate_batch: bool = False,
    donate_state: bool = True,
) -> Callable[[TrainState, Any], tuple[TrainState, dict]]:
    """Build `step(state, batch) -> (state, metrics)`, jitted with donated state.

    loss_fn(params, batch) must return (scalar_loss, metrics_dict).
    batch_spec (with mesh) pins the batch layout (e.g. P(("dp","fsdp"), "sp"));
    param_shardings keeps params pinned through the update.
    donate_batch=True also donates the batch buffers — safe when each batch
    array is consumed exactly once (a fresh device_put per step, e.g.
    ``DevicePrefetchIterator`` output), letting XLA reuse the input pages
    for the step's activations instead of allocating fresh ones.
    donate_state=False keeps state donation off: on the CPU backend the
    runtime BLOCKS the dispatch call until a donated input is defined
    (measured ~the full step time — dispatch degrades to synchronous), so
    CPU A/B harnesses of the async-dispatch tier opt out; on TPU, keep it
    on — aliasing is resolved asynchronously and halves HBM for the state.
    """

    def step_fn(state: TrainState, batch: Any) -> tuple[TrainState, dict]:
        if mesh is not None and batch_spec is not None:
            sh = NamedSharding(mesh, batch_spec)
            batch = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(x, sh), batch
            )
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (_, metrics), grads = grad_fn(state["params"], batch)
        with stage("optimizer"):
            updates, new_opt = optimizer.update(
                grads, state["opt_state"], state["params"]
            )
            new_params = optax.apply_updates(state["params"], updates)
            if param_shardings is not None:
                new_params = jax.tree.map(
                    lambda x, s: jax.lax.with_sharding_constraint(x, s),
                    new_params,
                    param_shardings,
                )
            new_state = {
                "params": new_params,
                "opt_state": new_opt,
                "step": state["step"] + 1,
            }
        return new_state, metrics

    donate = ()
    if donate_state:
        donate += (0,)
    if donate_batch:
        donate += (1,)
    return jax.jit(step_fn, donate_argnums=donate)


def compile_train_step(
    step: Callable, state: TrainState, batch: Any
) -> tuple[Callable, float | None]:
    """AOT-compile a jitted train step for these (state, batch) shapes.

    ``jit(...).lower().compile()`` during setup moves tracing AND XLA
    compilation out of the first step, so a measured window (or a
    latency-sensitive first batch) only ever contains device execution.
    Returns ``(compiled, flops_per_step)``: the compiled executable is
    called positionally, ``compiled(state, batch)``, with the same
    donation semantics the jit had; flops_per_step comes from the
    executable's own ``cost_analysis()`` — a device-verified number to
    cross-check tok/s against (None when the backend reports no cost
    model)."""
    compiled = step.lower(state, batch).compile()
    flops: float | None = None
    try:
        value = float((compiled.cost_analysis() or {}).get("flops", 0.0))
        flops = value if value > 0 else None
    except Exception:  # raylint: disable=RL006 -- cost model is advisory; backends without one must not fail setup
        flops = None
    return compiled, flops


def default_optimizer(
    lr: float = 3e-4,
    *,
    weight_decay: float = 0.1,
    warmup_steps: int = 100,
    total_steps: int = 10_000,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
) -> optax.GradientTransformation:
    """AdamW + cosine schedule + global-norm clipping (GPT-2 training recipe)."""
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=lr,
        warmup_steps=warmup_steps,
        decay_steps=max(total_steps, warmup_steps + 1),
        end_value=lr * 0.1,
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay),
    )
