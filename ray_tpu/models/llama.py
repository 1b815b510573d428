"""Llama-family decoder: RMSNorm + RoPE + GQA + SwiGLU, mesh-first.

Second model family of the compute tier (the reference has no model zoo of
its own — its llama path is `transformers` checkpoints under TorchTrainer /
vLLM; here the architecture is framework-native). Everything rides the same
infrastructure as GPT-2 (:mod:`ray_tpu.models.gpt2`):

- stacked layers under ``lax.scan`` (one compile any depth; the ``layers``
  dim is the pipeline axis — GPipe via the shared ``pipelined_blocks``),
- logical-axis sharding rules (tp/fsdp/pp/sp from the default rule table,
  grouped-KV heads replicated like the reference architectures shard them),
- the Pallas flash-attention kernel (KV heads broadcast to query heads
  before the kernel — correct GQA; a GQA-aware kernel variant is a later
  bandwidth optimization),
- the chunked LM loss (untied lm_head instead of wte^T).

Differences from GPT-2 by design: RMSNorm (no mean-centering, no bias),
rotary position embeddings (no learned wpe), SwiGLU MLP (3 matrices,
hidden 8/3·d rounded), no biases anywhere, untied output head.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from ray_tpu.models.common import _rms_norm, chunked_lm_loss, pipelined_blocks, stage
from ray_tpu.ops.attention import causal_attention, uses_flash_kernel

Params = dict


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    family: ClassVar[str] = "llama"  # what models/paged.py and the engine dispatch on

    vocab_size: int = 32000
    n_layer: int = 12
    n_head: int = 12
    n_kv_head: int = 4  # grouped-query attention (n_head % n_kv_head == 0)
    d_model: int = 768
    d_ff: int = 2048  # SwiGLU hidden (~8/3 * d rounded to 256)
    max_seq: int = 1024
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attn_impl: str = "auto"
    attn_block_q: int = 1024  # as GPT2Config's (a head of 128: 259 us against 424)
    attn_block_k: int = 1024
    remat: str = "mlp"  # same policy ladder as GPT2Config.remat
    loss_chunk: int = 128
    pipeline_microbatches: int = 0
    rms_eps: float = 1e-5

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_head == 0
        return self.d_model // self.n_head

    @property
    def kv_dim(self) -> int:
        assert self.n_head % self.n_kv_head == 0
        return self.n_kv_head * self.head_dim

    @staticmethod
    def llama_125m() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def tiny(
        n_layer: int = 2,
        d_model: int = 128,
        n_head: int = 4,
        n_kv_head: int = 2,
        vocab_size: int = 512,
        max_seq: int = 256,
    ) -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=vocab_size,
            n_layer=n_layer,
            n_head=n_head,
            n_kv_head=n_kv_head,
            d_model=d_model,
            d_ff=2 * d_model,
            max_seq=max_seq,
        )


def param_logical_specs(cfg: LlamaConfig) -> Params:
    L = ("layers",)
    return {
        "wte": ("vocab", "embed"),
        "blocks": {
            "attn_norm": L + ("norm",),
            "wq": L + ("embed", "mlp"),
            "wk": L + ("embed", "kv"),
            "wv": L + ("embed", "kv"),
            "wo": L + ("mlp", "embed"),
            "mlp_norm": L + ("norm",),
            "w_gate": L + ("embed", "mlp"),
            "w_up": L + ("embed", "mlp"),
            "w_down": L + ("mlp", "embed"),
        },
        "final_norm": ("norm",),
        "lm_head": ("embed", "vocab"),
    }


def init_params(key: jax.Array, cfg: LlamaConfig) -> Params:
    k = iter(jax.random.split(key, 12))
    pd = cfg.param_dtype
    L, D, F, V = cfg.n_layer, cfg.d_model, cfg.d_ff, cfg.vocab_size
    KD = cfg.kv_dim
    std = 0.02
    resid_std = std / (2 * L) ** 0.5

    def normal(key, shape, s=std):
        return (jax.random.normal(key, shape) * s).astype(pd)

    return {
        "wte": normal(next(k), (V, D)),
        "blocks": {
            "attn_norm": jnp.ones((L, D), pd),
            "wq": normal(next(k), (L, D, D)),
            "wk": normal(next(k), (L, D, KD)),
            "wv": normal(next(k), (L, D, KD)),
            "wo": normal(next(k), (L, D, D), resid_std),
            "mlp_norm": jnp.ones((L, D), pd),
            "w_gate": normal(next(k), (L, D, F)),
            "w_up": normal(next(k), (L, D, F)),
            "w_down": normal(next(k), (L, F, D), resid_std),
        },
        "final_norm": jnp.ones((D,), pd),
        "lm_head": normal(next(k), (D, V)),
    }


def rope_tables(cfg: LlamaConfig, seq: int):
    """(cos, sin) [S, head_dim/2] rotary tables."""
    half = cfg.head_dim // 2
    freqs = cfg.rope_theta ** (
        -jnp.arange(0, half, dtype=jnp.float32) / half
    )
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def _apply_rope(t, cos, sin):
    """t: [B, H, S, Dh]; HALF-SPLIT (GPT-NeoX/HF) rotary convention:
    dimension i pairs with dimension i + head_dim/2. Checkpoint
    converters from Meta-style INTERLEAVED RoPE weights must permute
    wq/wk accordingly."""
    t1, t2 = jnp.split(t, 2, axis=-1)
    c = cos[None, None].astype(t.dtype)
    s = sin[None, None].astype(t.dtype)
    return jnp.concatenate([t1 * c - t2 * s, t1 * s + t2 * c], axis=-1)


def _attn_sublayer(x, p, cfg: LlamaConfig, cos, sin, mesh=None, ring=False):
    B, S, D = x.shape
    H, KH, Dh = cfg.n_head, cfg.n_kv_head, cfg.head_dim

    def heads(t, n):
        return t.reshape(B, S, n, Dh).transpose(0, 2, 1, 3)

    with stage("attn_proj"):
        h = _rms_norm(x, p["attn_norm"], cfg.rms_eps)
        q = h @ p["wq"].astype(cfg.dtype)
        kk = h @ p["wk"].astype(cfg.dtype)
        v = h @ p["wv"].astype(cfg.dtype)
        q = _apply_rope(heads(q, H), cos, sin)
        kk = _apply_rope(heads(kk, KH), cos, sin)
        v = heads(v, KH)
        # GQA: broadcast each KV head to its query-head group for the kernel.
        group = H // KH
        kk = jnp.repeat(kk, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    with stage("attn_core"):
        if ring:
            # Sequence sharded over sp: ring attention keeps K/V distributed,
            # rotating chunks over ICI (same dispatch as gpt2._attn_sublayer).
            from ray_tpu.ops.ring_attention import ring_attention

            attn = ring_attention(q, kk, v, mesh=mesh)
        else:
            attn = causal_attention(
                q, kk, v,
                impl=cfg.attn_impl,
                block_q=cfg.attn_block_q,
                block_k=cfg.attn_block_k,
                mesh=mesh,
            )
    with stage("attn_proj"):
        attn = attn.transpose(0, 2, 1, 3).reshape(B, S, D)
        return x + attn @ p["wo"].astype(cfg.dtype)


@stage("mlp")
def _mlp_sublayer(x, p, cfg: LlamaConfig):
    h = _rms_norm(x, p["mlp_norm"], cfg.rms_eps)
    gate = h @ p["w_gate"].astype(cfg.dtype)
    up = h @ p["w_up"].astype(cfg.dtype)
    return x + (jax.nn.silu(gate) * up) @ p["w_down"].astype(cfg.dtype)


def kv_hooks(cfg: LlamaConfig, S: int):
    """The hook table :mod:`ray_tpu.models.paged` serves this family
    through (``paged.family``): RoPE by gathered absolute position, keys and
    values for the ``n_kv_head`` heads unexpanded."""
    H, KH, Dh = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    with stage("attn_proj"):
        cos_full, sin_full = rope_tables(cfg, S)

    @stage("embed_head")
    def embed(params, tokens, pos2d):
        return params["wte"].astype(cfg.dtype)[tokens]

    @stage("attn_proj")
    def qkv(x, p, pos2d):
        B, T, _ = x.shape
        h = _rms_norm(x, p["attn_norm"], cfg.rms_eps)
        q = (h @ p["wq"].astype(cfg.dtype)).reshape(B, T, H, Dh)
        k = (h @ p["wk"].astype(cfg.dtype)).reshape(B, T, KH, Dh)
        v = (h @ p["wv"].astype(cfg.dtype)).reshape(B, T, KH, Dh)
        cos = cos_full[pos2d][:, :, None, :]  # [B, T, 1, half]
        sin = sin_full[pos2d][:, :, None, :]

        def rope(t):
            t1, t2 = jnp.split(t, 2, axis=-1)
            c = cos.astype(t.dtype)
            s = sin.astype(t.dtype)
            return jnp.concatenate(
                [t1 * c - t2 * s, t1 * s + t2 * c], axis=-1
            )

        heads = lambda t: t.transpose(0, 2, 1, 3)
        return heads(rope(q)), heads(rope(k)), heads(v)

    def finish(x, attn, p):  # attn [B, H, T, Dh]
        B, Hh, T, _ = attn.shape
        with stage("attn_proj"):
            a = attn.transpose(0, 2, 1, 3).reshape(B, T, cfg.d_model)
            x = x + a @ p["wo"].astype(cfg.dtype)
        return _mlp_sublayer(x, p, cfg)

    @stage("embed_head")
    def final(params, last):  # last [B, D] -> [B, vocab] f32
        h = _rms_norm(last, params["final_norm"], cfg.rms_eps)
        return (h @ params["lm_head"].astype(cfg.dtype)).astype(
            jnp.float32
        )

    return embed, qkv, finish, final, H, KH, Dh


def hidden(
    params: Params, tokens: jax.Array, cfg: LlamaConfig, mesh=None
) -> jax.Array:
    """tokens [B, S] -> final-RMSNorm hidden [B, S, D]."""
    B, S = tokens.shape
    pp_size = mesh.shape.get("pp", 1) if mesh is not None else 1
    pipelined = pp_size > 1 and cfg.pipeline_microbatches > 0
    if pipelined and jax.default_backend() == "cpu":
        # Same XLA:CPU bf16-allreduce workaround as the GPT-2 pipeline.
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    sp_size = mesh.shape.get("sp", 1) if mesh is not None else 1
    with stage("embed_head"):
        x = params["wte"].astype(cfg.dtype)[tokens]
    with stage("attn_proj"):
        cos, sin = rope_tables(cfg, S)
    remat = cfg.remat
    # No mesh for attention inside the pp pipeline (same as gpt2.hidden).
    attn_mesh = None if pipelined else mesh
    uses_ring = attn_mesh is not None and sp_size > 1 and S % sp_size == 0
    attn = {"mesh": attn_mesh, "ring": uses_ring}
    if remat == "mlp" and (
        uses_ring
        or not uses_flash_kernel(
            S, impl=cfg.attn_impl,
            block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
            mesh=attn_mesh,
        )
    ):
        remat = "dots"  # same rationale as gpt2.hidden
    dots_policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable

    def block(x, p):
        return (
            _mlp_sublayer(
                _attn_sublayer(x, p, cfg, cos, sin, **attn), p, cfg
            ),
            jnp.zeros((), jnp.float32),
        )

    if remat == "full":
        block_fn = jax.checkpoint(block)
    elif remat == "dots":
        block_fn = jax.checkpoint(block, policy=dots_policy)
    elif remat == "mlp":
        mlp_ckpt = jax.checkpoint(
            functools.partial(_mlp_sublayer, cfg=cfg), policy=dots_policy
        )

        def block_fn(x, p):
            return (
                mlp_ckpt(
                    _attn_sublayer(x, p, cfg, cos, sin, **attn), p
                ),
                jnp.zeros((), jnp.float32),
            )

    elif remat == "none":
        block_fn = block
    else:
        raise ValueError(f"unknown remat policy {cfg.remat!r}")

    if pipelined:
        x, _aux = pipelined_blocks(
            params["blocks"], x, block_fn, mesh,
            n_micro=cfg.pipeline_microbatches,
        )
    else:
        x, _aux = jax.lax.scan(block_fn, x, params["blocks"])
    with stage("embed_head"):
        return _rms_norm(x, params["final_norm"], cfg.rms_eps)


def forward(
    params: Params, tokens: jax.Array, cfg: LlamaConfig, mesh=None
) -> jax.Array:
    """tokens [B, S] -> logits [B, S, vocab]."""
    x = hidden(params, tokens, cfg, mesh=mesh)
    with stage("embed_head"):
        return x @ params["lm_head"].astype(cfg.dtype)


def loss_fn(
    params: Params, batch: dict, cfg: LlamaConfig, mesh=None
) -> tuple[jax.Array, dict]:
    """Next-token cross-entropy; same batch contract as gpt2.loss_fn."""
    tokens = batch["tokens"]
    if "targets" in batch:
        inputs, targets = tokens, batch["targets"]
    else:
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = hidden(params, inputs, cfg, mesh=mesh)
    with stage("embed_head"):
        head = params["lm_head"].astype(cfg.dtype)
        if cfg.loss_chunk and inputs.shape[1] > cfg.loss_chunk:
            # chunked_lm_loss expects the head oriented [V, D]; lm_head is
            # [D, V] — hand it transposed (fuses into the matmul under jit).
            total = chunked_lm_loss(x, head.T, targets, cfg.loss_chunk)
            ce = total / targets.size
        else:
            logits = (x @ head).astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
            ce = jnp.mean(lse - tgt)
    return ce, {"loss": ce, "tokens": jnp.array(targets.size, jnp.int32)}


def num_params(cfg: LlamaConfig) -> int:
    D, F, V, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_layer
    KD = cfg.kv_dim
    per_layer = 2 * D + D * D + 2 * D * KD + D * D + 3 * D * F
    return V * D + L * per_layer + D + D * V
