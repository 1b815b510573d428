"""GPT-2 as the benchmark reaches it: trained through ``make_train_step``
under the job's mesh. Configurations use the published key names (``n_embd``,
``n_head`` ...) and hold the vocabulary rows actually kept under
``assumed.padded_vocab_size``; the plain reference is ``reference/gpt2_ref.py``.

Provides ``model_config``, ``check``, ``shrink`` and what a training family
owes the train driver and the utilisation reader: ``init_params``,
``loss_fn``, ``forward``, ``param_logical_specs``, ``train_flops_per_token``,
``num_params`` (see README.md, "A family").
"""

from __future__ import annotations

import functools

CHECK_SEQUENCES = 4  # one per fsdp shard


def model_config(c: dict, traffic: dict):
    import jax.numpy as jnp

    from ray_tpu.models.gpt2 import GPT2Config

    d = c["n_embd"]
    assert traffic["seq_len"] <= c["n_positions"]
    return GPT2Config(
        vocab_size=c["assumed"]["padded_vocab_size"],
        n_layer=c["n_layer"],
        n_head=c["n_head"],
        d_model=d,
        d_ff=c.get("n_inner") or 4 * d,
        max_seq=c["n_positions"],
        dtype=jnp.dtype(c["dtype"]),
        param_dtype=jnp.dtype(c["param_dtype"]),
        attn_impl=traffic["attn_impl"],
        remat=traffic["remat"],
        loss_chunk=traffic["loss_chunk"],
    )


def init_params(key, cfg):
    from ray_tpu.models import gpt2

    return gpt2.init_params(key, cfg)


def loss_fn(params, batch, cfg, mesh=None):
    from ray_tpu.models import gpt2

    return gpt2.loss_fn(params, batch, cfg, mesh=mesh)


def forward(params, tokens, cfg, mesh=None):
    from ray_tpu.models import gpt2

    return gpt2.forward(params, tokens, cfg, mesh=mesh)


def param_logical_specs(cfg):
    from ray_tpu.models import gpt2

    return gpt2.param_logical_specs(cfg)


def shrink(c: dict) -> dict:
    """The tiny keys of a CPU rehearsal."""
    return {**c, "n_embd": 128, "n_head": 4, "n_layer": 2, "n_positions": 128, "n_ctx": 128,
            "assumed": {**c["assumed"], "padded_vocab_size": 512}}


def check(c: dict, traffic: dict, seed: int, who: str, devices=None) -> dict:
    """``program`` is the loss function's loss, gradients and logits under the
    job's mesh on seeded sequences; ``fp8`` and ``bf16`` put the reference
    computed in that precision in its place."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.reference import gpt2_ref
    from benchmarks.reference.common import rel_err
    from ray_tpu.parallel import DEFAULT_RULES, MeshSpec, make_mesh, shardings_from_logical

    cfg = model_config(c, traffic)
    mesh = make_mesh(MeshSpec(**traffic["mesh"]), devices or jax.devices())
    shardings = shardings_from_logical(param_logical_specs(cfg), DEFAULT_RULES, mesh)
    weights = jax.device_put(gpt2_ref.init_weights(seed, c), shardings)
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, size=(CHECK_SEQUENCES, traffic["seq_len"])).astype(np.int32)
    batch = jax.device_put(
        {"tokens": toks, "targets": np.roll(toks, -1, axis=1)},
        NamedSharding(mesh, P(("dp", "fsdp"))),
    )
    ref_grads = jax.jit(functools.partial(gpt2_ref.loss_and_grads, c=c, quant=None))
    ref_logits = jax.jit(functools.partial(gpt2_ref.forward, c=c, quant=None))
    want_loss, want_grads = ref_grads(weights, batch)
    if who in ("fp8", "bf16"):
        got_loss, got_grads = jax.jit(
            functools.partial(gpt2_ref.loss_and_grads, c=c, quant=who)
        )(weights, batch)
        got_logits = jax.jit(functools.partial(gpt2_ref.forward, c=c, quant=who))(
            weights, batch["tokens"]
        )
    elif who == "program":
        (got_loss, _m), got_grads = jax.jit(
            jax.value_and_grad(
                lambda p, b: loss_fn(p, b, cfg, mesh=mesh), has_aux=True
            )
        )(weights, batch)
        got_logits = jax.jit(lambda p, t: forward(p, t, cfg, mesh=mesh))(
            weights, batch["tokens"]
        )
    else:
        raise SystemExit(f"unknown --who {who!r}")
    out = {"grad_rel_err": rel_err(got_grads, want_grads)}
    del got_grads, want_grads
    out["logits_rel_err"] = rel_err(got_logits, ref_logits(weights, batch["tokens"]))
    out["loss"] = [float(got_loss), float(want_loss)]
    return out


# -- operations that the algorithm needs (flops_bytes.py says what "needs" means)


def layer_matmul_params(c: dict) -> int:
    d = c["n_embd"]
    f = c.get("n_inner") or 4 * d
    return d * 3 * d + d * d + 2 * d * f


def train_flops_per_token(c: dict, traffic: dict) -> float:
    """Forward and backward operations one training token requires: three
    times the forward pass (two for the backward), recomputation not counted.
    The tied head multiplies by all the vocabulary rows held (padding included,
    since its logits enter the softmax)."""
    d, L, vocab_rows = c["n_embd"], c["n_layer"], c["assumed"]["padded_vocab_size"]
    matmul = 2 * (L * layer_matmul_params(c) + vocab_rows * d)
    # Causal attention: a query at position i sees i + 1 keys; the mean over
    # a sequence is (S + 1) / 2. QK^T and PV each cost 2 * d per key.
    attn = L * 2 * 2 * d * (traffic["seq_len"] + 1) / 2
    return 3 * (matmul + attn)


def num_params(c: dict) -> int:
    d, L, vocab_rows = c["n_embd"], c["n_layer"], c["assumed"]["padded_vocab_size"]
    f = c.get("n_inner") or 4 * d
    per_layer = layer_matmul_params(c) + 4 * d + 3 * d + d + f + d
    return vocab_rows * d + c["n_positions"] * d + L * per_layer + 2 * d
