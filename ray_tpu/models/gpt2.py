"""GPT-2 in pure JAX, built mesh-first.

Flagship model of the framework (north star: GPT-2-125M data-parallel on a
v4 pod — BASELINE.md). Design choices that differ from a torch port:

- Layers are *stacked* along a leading ``layers`` dim and executed with
  ``lax.scan``: one trace/compile regardless of depth, and the ``layers`` dim
  is itself shardable (pipeline axis).
- Every parameter carries a tuple of *logical* axis names
  (see :mod:`ray_tpu.parallel.sharding`); tensor/fsdp/pipeline parallelism is
  a rule-table choice, not a model change.
- bfloat16 activations / float32 params+optimizer by default (MXU-native).
- Attention dispatches to the Pallas flash kernel on TPU
  (:mod:`ray_tpu.ops.attention`).

Reference parity note: the reference has no model zoo of its own; its GPT-2
path is `transformers` + TorchTrainer (reference:
python/ray/train/examples/transformers/). Here the model is framework-native.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from ray_tpu.models.common import chunked_lm_loss, pipelined_blocks, stage
from ray_tpu.ops.attention import causal_attention, uses_flash_kernel

# Back-compat aliases (pre-round-4 private names)
_chunked_lm_loss = chunked_lm_loss
_pipelined_blocks = pipelined_blocks

Params = dict


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    family: ClassVar[str] = "gpt2"  # what models/paged.py and the engine dispatch on

    vocab_size: int = 50304  # 50257 rounded up to a multiple of 128 (lane tiling)
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_seq: int = 1024
    dtype: Any = jnp.bfloat16  # activation dtype
    param_dtype: Any = jnp.float32
    attn_impl: str = "auto"  # "auto" | "pallas" | "reference"
    # Flash kernel block sizes, fitted to S at dispatch
    # (ops/attention.py:_fit_block). Equal blocks have their diagonal tile
    # cut into row groups; on a v5e at S=1024, D=64 one block of 1024 a head
    # takes 417 us a call forward+backward where two of 512 take 554 and two
    # of 512 scored whole 671 (PERF.md section 6, PR 41).
    attn_block_q: int = 1024
    attn_block_k: int = 1024
    # Rematerialization policy for the per-layer scan:
    #   "full"  — recompute the whole block in backward (min memory, +FLOPs)
    #   "dots"  — save weight-matmul outputs, recompute attention/gelu/norms
    #             (jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    #   "mlp"   — attention sublayer not checkpointed (its flash-kernel
    #             residuals are saved, so backward never re-runs the forward
    #             kernel); MLP checkpointed with the dots policy
    #   "none"  — save everything XLA wants (max memory)
    # bools accepted for back-compat: True == "full", False == "none".
    remat: bool | str = "mlp"
    # LM-head loss chunking: SEQUENCE positions per chunk for the
    # logits/cross-entropy computation. The full [B, S, vocab] logits tensor
    # (and its gradient) dominates HBM at train batch sizes — 3.3 GB each at
    # B=32, S=1024 — so the loss scans over sequence chunks and
    # REMATERIALIZES each chunk's logits in backward. 0 disables chunking.
    loss_chunk: int = 128
    # Pipeline parallelism: number of microbatches for the GPipe schedule
    # over the mesh's `pp` axis (0 = no pipelining). Takes effect when
    # loss_fn/hidden receive a mesh whose pp axis is >1; the stacked layers
    # dim is split into pp stages and activations rotate between stages
    # via ppermute (SURVEY §2.4: the reference has NO native pp — this is
    # the TPU-native differentiator).
    pipeline_microbatches: int = 0
    # Mixture-of-experts: replaces the dense MLP sublayer with a top-1
    # switch layer of n_experts experts (0 = dense). Experts shard over the
    # mesh's `ep` axis via the "experts" logical rule. moe_aux_weight
    # scales the Switch load-balancing loss (E * sum_e f_e * P_e) — without
    # it top-1 routing collapses onto one expert.
    n_experts: int = 0
    expert_capacity_factor: float = 1.5
    moe_aux_weight: float = 0.01

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_head == 0
        return self.d_model // self.n_head

    @staticmethod
    def gpt2_125m() -> "GPT2Config":
        return GPT2Config()

    @staticmethod
    def tiny(
        n_layer: int = 2,
        d_model: int = 128,
        n_head: int = 4,
        vocab_size: int = 512,
        max_seq: int = 256,
    ) -> "GPT2Config":
        return GPT2Config(
            vocab_size=vocab_size,
            n_layer=n_layer,
            n_head=n_head,
            d_model=d_model,
            d_ff=4 * d_model,
            max_seq=max_seq,
        )


def param_logical_specs(cfg: GPT2Config) -> Params:
    """Logical axis names per parameter (leaves are tuples of names)."""
    L = ("layers",)
    if cfg.n_experts > 0:
        ffn = {
            "gate_w": L + ("embed", "norm"),  # tiny; replicate
            "exp_w1": L + ("experts", "embed", "mlp"),
            "exp_b1": L + ("experts", "mlp"),
            "exp_w2": L + ("experts", "mlp", "embed"),
            "exp_b2": L + ("norm",),
        }
    else:
        ffn = {
            "fc_w": L + ("embed", "mlp"),
            "fc_b": L + ("mlp",),
            "fc2_w": L + ("mlp", "embed"),
            "fc2_b": L + ("norm",),
        }
    return {
        "wte": ("vocab", "embed"),
        "wpe": ("seq_param", "embed"),
        "blocks": {
            "ln1_scale": L + ("norm",),
            "ln1_bias": L + ("norm",),
            "qkv_w": L + ("embed", "mlp"),
            "qkv_b": L + ("mlp",),
            "proj_w": L + ("mlp", "embed"),
            "proj_b": L + ("norm",),
            "ln2_scale": L + ("norm",),
            "ln2_bias": L + ("norm",),
            **ffn,
        },
        "lnf_scale": ("norm",),
        "lnf_bias": ("norm",),
    }


def init_params(key: jax.Array, cfg: GPT2Config) -> Params:
    """GPT-2 initialization: N(0, 0.02), residual projections scaled by
    1/sqrt(2*n_layer), zeros for biases, ones for LN scales."""
    k = iter(jax.random.split(key, 8))
    std = 0.02
    pd = cfg.param_dtype
    L, D, F, V, S = cfg.n_layer, cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.max_seq
    resid_std = std / (2 * L) ** 0.5

    def normal(key, shape, s):
        return (jax.random.normal(key, shape) * s).astype(pd)

    if cfg.n_experts > 0:
        E = cfg.n_experts
        ffn = {
            "gate_w": normal(next(k), (L, D, E), std),
            "exp_w1": normal(next(k), (L, E, D, F), std),
            "exp_b1": jnp.zeros((L, E, F), pd),
            "exp_w2": normal(next(k), (L, E, F, D), resid_std),
            "exp_b2": jnp.zeros((L, D), pd),
        }
    else:
        ffn = {
            "fc_w": normal(next(k), (L, D, F), std),
            "fc_b": jnp.zeros((L, F), pd),
            "fc2_w": normal(next(k), (L, F, D), resid_std),
            "fc2_b": jnp.zeros((L, D), pd),
        }
    return {
        "wte": normal(next(k), (V, D), std),
        "wpe": normal(next(k), (S, D), std),
        "blocks": {
            "ln1_scale": jnp.ones((L, D), pd),
            "ln1_bias": jnp.zeros((L, D), pd),
            "qkv_w": normal(next(k), (L, D, 3 * D), std),
            "qkv_b": jnp.zeros((L, 3 * D), pd),
            "proj_w": normal(next(k), (L, D, D), resid_std),
            "proj_b": jnp.zeros((L, D), pd),
            "ln2_scale": jnp.ones((L, D), pd),
            "ln2_bias": jnp.zeros((L, D), pd),
            **ffn,
        },
        "lnf_scale": jnp.ones((D,), pd),
        "lnf_bias": jnp.zeros((D,), pd),
    }


def _layer_norm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


@stage("attn_proj")
def _qkv(x, p, cfg: GPT2Config):
    """x [B, T, D] -> q, k, v, each [B, H, T, Dh]."""
    B, T, D = x.shape
    h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])
    qkv = h @ p["qkv_w"].astype(cfg.dtype) + p["qkv_b"].astype(cfg.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(B, T, cfg.n_head, cfg.head_dim).transpose(0, 2, 1, 3)

    return heads(q), heads(k), heads(v)


@stage("attn_proj")
def _attn_out(x, attn, p, cfg: GPT2Config):
    """attn [B, H, T, Dh] through the output projection, onto x."""
    B, H, T, Dh = attn.shape
    attn = attn.transpose(0, 2, 1, 3).reshape(B, T, H * Dh)
    return x + attn @ p["proj_w"].astype(cfg.dtype) + p["proj_b"].astype(cfg.dtype)


def _attn_sublayer(x, p, cfg: GPT2Config, mesh=None, ring=False):
    q, k_, v = _qkv(x, p, cfg)
    with stage("attn_core"):
        if ring:
            # Sequence sharded over sp: ring attention keeps K/V distributed
            # and rotates chunks over ICI instead of letting XLA re-gather the
            # full sequence per chip (SURVEY §5.7 — must-build).
            from ray_tpu.ops.ring_attention import ring_attention

            attn = ring_attention(q, k_, v, mesh=mesh)
        else:
            attn = causal_attention(
                q,
                k_,
                v,
                impl=cfg.attn_impl,
                block_q=cfg.attn_block_q,
                block_k=cfg.attn_block_k,
                mesh=mesh,
            )
    return _attn_out(x, attn, p, cfg)


@stage("mlp")
def _mlp_sublayer(x, p, cfg: GPT2Config):
    h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"])
    h = h @ p["fc_w"].astype(cfg.dtype) + p["fc_b"].astype(cfg.dtype)
    h = jax.nn.gelu(h, approximate=True)
    return x + h @ p["fc2_w"].astype(cfg.dtype) + p["fc2_b"].astype(cfg.dtype)


def kv_hooks(cfg: GPT2Config, S: int):
    """The hook table :mod:`ray_tpu.models.paged` serves this family
    through (``paged.family``): learned positions, one key/value head a
    query head, the dense MLP."""
    H, Dh = cfg.n_head, cfg.head_dim

    @stage("embed_head")
    def embed(params, tokens, pos2d):
        return (
            params["wte"].astype(cfg.dtype)[tokens]
            + params["wpe"].astype(cfg.dtype)[pos2d]
        )

    def qkv(x, p, pos2d):
        return _qkv(x, p, cfg)

    def finish(x, attn, p):
        return _mlp_sublayer(_attn_out(x, attn, p, cfg), p, cfg)

    @stage("embed_head")
    def final(params, last):
        h = _layer_norm(last, params["lnf_scale"], params["lnf_bias"])
        return (h @ params["wte"].astype(cfg.dtype).T).astype(jnp.float32)

    return embed, qkv, finish, final, H, H, Dh


def _moe_sublayer(x, p, cfg: GPT2Config):
    """Top-1 switch MoE (Fedus et al.) replacing the dense MLP: softmax
    gate routes each token to one expert under a capacity limit; dropped
    tokens pass through the residual unchanged. The expert dim of
    exp_w1/exp_w2 carries the "experts" logical axis -> `ep` mesh axis, so
    the dispatch/combine einsums compile to all-to-alls over ep.

    Dense one-hot dispatch ([N, E, C] tensors) — simple and correct, sized
    for the test/dryrun scale; a production MoE would sort-and-gather.
    """
    B, S, D = x.shape
    E = cfg.n_experts
    # XLA:CPU's AllReducePromotion pass crashes on the bf16 all-reduces the
    # ep-sharded einsums (and their backward) produce; compute the expert
    # path in f32 on CPU (virtual-mesh tests/dryrun). Real TPUs keep bf16.
    cdt = jnp.float32 if jax.default_backend() == "cpu" else cfg.dtype
    N = B * S
    cap = max(int(cfg.expert_capacity_factor * N / E), 1)

    with stage("router"):
        h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"])
        hf = h.reshape(B * S, D).astype(cdt)
        logits = (hf @ p["gate_w"].astype(cdt)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)  # [N, E]
        gate = jnp.max(probs, axis=-1)
        expert = jnp.argmax(probs, axis=-1)
        onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)  # [N, E]
        # Switch load-balancing auxiliary loss: E * sum_e f_e * P_e, where f is
        # the (pre-capacity) routed fraction and P the mean router probability.
        # Minimized at uniform routing; without it top-1 collapses.
        aux = E * jnp.sum(
            jnp.mean(onehot, axis=0) * jnp.mean(probs, axis=0)
        )
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot
        onehot = onehot * (pos < cap)  # over-capacity tokens dropped
        dispatch = onehot[..., None] * jax.nn.one_hot(
            pos.astype(jnp.int32), cap, dtype=jnp.float32
        )  # [N, E, C]
        combine = dispatch * gate[:, None, None]
        xe = jnp.einsum("nd,nec->ecd", hf, dispatch.astype(cdt))
    with stage("experts"):
        he = jnp.einsum("ecd,edf->ecf", xe, p["exp_w1"].astype(cdt))
        he = jax.nn.gelu(
            he + p["exp_b1"].astype(cdt)[:, None, :], approximate=True
        )
        ye = jnp.einsum("ecf,efd->ecd", he, p["exp_w2"].astype(cdt))
    with stage("router"):
        y = jnp.einsum("ecd,nec->nd", ye, combine.astype(cdt))
        # Output bias only for tokens an expert actually served — dropped
        # (over-capacity) tokens pass through the residual truly unchanged.
        routed = jnp.sum(onehot, axis=-1, keepdims=True).astype(cdt)  # [N, 1]
        y = y + p["exp_b2"].astype(cdt) * routed
        return x + y.reshape(B, S, D).astype(x.dtype), aux


def _block(x, p, cfg: GPT2Config, mesh=None, ring=False):
    """One transformer block -> (x, moe_aux). x: [B, S, D]; p: one layer's
    params; moe_aux is 0 for dense layers."""
    h = _attn_sublayer(x, p, cfg, mesh=mesh, ring=ring)
    if cfg.n_experts > 0:
        return _moe_sublayer(h, p, cfg)
    return _mlp_sublayer(h, p, cfg), jnp.zeros((), jnp.float32)


def hidden(
    params: Params,
    tokens: jax.Array,
    cfg: GPT2Config,
    mesh=None,
) -> jax.Array:
    """tokens [B, S] int32 -> final-LN hidden states [B, S, d_model].

    With ``mesh`` whose `pp` axis is >1 and cfg.pipeline_microbatches > 0,
    the stacked-layers scan runs as a GPipe pipeline over pp stages.
    Returns (x, moe_aux): the summed Switch load-balancing loss (0 when
    dense)."""
    B, S = tokens.shape
    pp_size = mesh.shape.get("pp", 1) if mesh is not None else 1
    sp_size = mesh.shape.get("sp", 1) if mesh is not None else 1
    pipelined = pp_size > 1 and cfg.pipeline_microbatches > 0
    if pipelined and jax.default_backend() == "cpu":
        # XLA:CPU's AllReducePromotion crashes on the bf16 all-reduces the
        # pipeline's backward emits; the virtual-mesh tests/dryrun run this
        # section in f32. Real TPUs keep bf16.
        import dataclasses as _dc

        cfg = _dc.replace(cfg, dtype=jnp.float32)
    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[tokens]
        x = x + params["wpe"].astype(cfg.dtype)[:S][None]

    remat = {True: "full", False: "none"}.get(cfg.remat, cfg.remat)
    if remat == "mlp" and cfg.n_experts > 0:
        remat = "dots"  # the "mlp" policy checkpoints the DENSE sublayer
    # Inside the pp pipeline's shard_map attention gets no mesh: neither the
    # ring (a shard_map over sp) nor the per-shard kernel wrapper nests
    # there, so it is a plain call that XLA reshards around.
    attn_mesh = None if pipelined else mesh
    uses_ring = attn_mesh is not None and sp_size > 1 and S % sp_size == 0
    if remat == "mlp" and (
        uses_ring
        or not uses_flash_kernel(
            S,
            impl=cfg.attn_impl,
            block_q=cfg.attn_block_q,
            block_k=cfg.attn_block_k,
            mesh=attn_mesh,
        )
    ):
        # "mlp" exists to preserve the flash kernel's o/lse residuals. On
        # the jnp reference path AND the ring path there is no custom_vjp
        # kernel, and leaving attention un-checkpointed would stack
        # O(L*B*H*S^2[/sp]) softmax residuals.
        remat = "dots"
    attn = {"mesh": attn_mesh, "ring": uses_ring}
    dots_policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if remat == "full":
        block_fn = jax.checkpoint(
            functools.partial(_block, cfg=cfg, **attn)
        )
    elif remat == "dots":
        block_fn = jax.checkpoint(
            functools.partial(_block, cfg=cfg, **attn),
            policy=dots_policy,
        )
    elif remat == "mlp":
        # Attention stays outside the checkpoint so the flash kernel's saved
        # residuals (o, lse) survive to backward — custom_vjp residuals are
        # invisible to checkpoint policies, so any checkpoint around the
        # attention call forces a forward-kernel re-run in backward.
        mlp_ckpt = jax.checkpoint(
            functools.partial(_mlp_sublayer, cfg=cfg), policy=dots_policy
        )

        def block_fn(x, layer_params):
            out = mlp_ckpt(
                _attn_sublayer(x, layer_params, cfg, **attn),
                layer_params,
            )
            return out, jnp.zeros((), jnp.float32)

    elif remat == "none":
        block_fn = functools.partial(_block, cfg=cfg, **attn)
    else:
        raise ValueError(f"unknown remat policy {cfg.remat!r}")

    def scan_body(x, layer_params):
        return block_fn(x, layer_params)  # (carry, per-layer aux)

    if pipelined:
        x, aux = pipelined_blocks(
            params["blocks"], x, block_fn, mesh,
            n_micro=cfg.pipeline_microbatches,
        )
    else:
        x, aux_layers = jax.lax.scan(scan_body, x, params["blocks"])
        aux = jnp.sum(aux_layers)
    with stage("embed_head"):
        return _layer_norm(x, params["lnf_scale"], params["lnf_bias"]), aux



def forward(
    params: Params, tokens: jax.Array, cfg: GPT2Config, mesh=None
) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, vocab] (activation dtype).
    Tied embeddings: logits = x @ wte^T (vocab-parallel under tp rules)."""
    x, _aux = hidden(params, tokens, cfg, mesh=mesh)
    with stage("embed_head"):
        return x @ params["wte"].astype(cfg.dtype).T



def loss_fn(
    params: Params, batch: dict, cfg: GPT2Config, mesh=None
) -> tuple[jax.Array, dict]:
    """Next-token cross-entropy. batch: {"tokens": [B, S+1] int32} or
    {"tokens": [B,S], "targets": [B,S]}."""
    tokens = batch["tokens"]
    if "targets" in batch:
        inputs, targets = tokens, batch["targets"]
    else:
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x, moe_aux = hidden(params, inputs, cfg, mesh=mesh)
    with stage("embed_head"):
        if cfg.loss_chunk and inputs.shape[1] > cfg.loss_chunk:
            total = chunked_lm_loss(
                x,
                params["wte"].astype(cfg.dtype),
                targets,
                cfg.loss_chunk,
            )
            ce = total / targets.size
        else:
            logits = (x @ params["wte"].astype(cfg.dtype).T).astype(jnp.float32)
            # Cross-entropy as logsumexp - target_logit: both reduce over
            # vocab, so XLA fuses the f32 upcast into the reductions and never
            # materializes an f32 [B, S, vocab] log-prob tensor.
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
            ce = jnp.mean(lse - tgt)
        loss = ce
        metrics = {"loss": ce, "tokens": jnp.array(targets.size, jnp.int32)}
        if cfg.n_experts > 0:
            loss = ce + cfg.moe_aux_weight * moe_aux
            metrics["moe_aux"] = moe_aux
    return loss, metrics


def num_params(cfg: GPT2Config) -> int:
    V, D, F, L, S = cfg.vocab_size, cfg.d_model, cfg.d_ff, cfg.n_layer, cfg.max_seq
    per_layer = 4 * D + (D * 3 * D + 3 * D) + (D * D + D) + (D * F + F) + (F * D + D)
    return V * D + S * D + L * per_layer + 2 * D
