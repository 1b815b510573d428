"""Solar Open 2's programs on the CPU at a tiny size, against the plain
reference (benchmarks/reference/solar_open2_ref.py, which imports nothing of
the program): whole prefill, prefill in chunks that carry the state and the
tail, prefill then decode (logits, the rows of keys and values where they lie,
the state and the tail by slot), a slot that held another sequence, the gated
attention without rotation by the gather and by the kernel, ``beta`` in (0, 2)
through the one KDA implementation it shares with Kimi Linear, and the eight
shares of an expert layer adding up to the uncut layer.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import solar_open2_ref as ref  # noqa: E402
from ray_tpu.models import kda, kimi_linear as kl, paged, solar_open2 as so  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402

pytestmark = pytest.mark.timeout(300)


def ref_config(cfg: so.SolarOpen2Config) -> dict:
    """The reference's dictionary of published keys for ``cfg``."""
    return dict(
        hidden_size=cfg.d_model, vocab_size=cfg.vocab_size, num_hidden_layers=cfg.n_layer,
        gqa_layers=[i for i, kind in enumerate(cfg.layer_kinds) if kind == so.GQA],
        num_attention_heads=cfg.n_head, num_key_value_heads=cfg.n_kv_head, head_dim=cfg.head_dim,
        use_rope=False, use_gqa_gate=True, rope_theta=10000, kda_use_full_proj=False,
        kda_allow_neg_eigval=cfg.kda_neg_eigval,
        moe_intermediate_size=cfg.moe_d_ff, n_routed_experts=cfg.experts_held,
        expert_offset=cfg.expert_offset, num_experts_per_tok=cfg.experts_per_token,
        n_shared_experts=cfg.n_shared_experts, first_k_dense_replace=0,
        norm_topk_prob=cfg.renormalize, routed_scaling_factor=cfg.routed_scaling,
        rms_norm_eps=cfg.rms_eps, param_dtype="float32", dtype="float32",
        linear_attn_config=dict(
            num_heads=cfg.kda_heads, num_kv_heads=None, head_dim=cfg.kda_head_dim,
            short_conv_kernel_size=cfg.conv_kernel,
        ),
        assumed=dict(kda_gate_rank=cfg.kda_gate_rank),
        published=dict(n_routed_experts=cfg.n_experts),
    )


@pytest.fixture(scope="module")
def tiny():
    cfg = so.SolarOpen2Config.tiny()
    return cfg, so.init_params(jax.random.key(0), cfg)


def test_the_configuration_is_the_published_model():
    """The defaults are the published sizes, uncut; they count the 250.29 B
    parameters, 14.74 B of them active a token, of the model's name."""
    cfg = so.SolarOpen2Config()
    assert cfg.layer_kinds[:5] == (so.GQA, so.KDA, so.KDA, so.KDA, so.GQA)
    assert (cfg.n_layer, cfg.layers_of(so.GQA), cfg.layers_of(so.KDA)) == (48, 12, 36)
    assert [i for i, k in enumerate(cfg.layer_kinds) if k == so.GQA] == list(range(0, 48, 4))
    assert cfg.kda_neg_eigval and cfg.n_moe_layers == 48 and cfg.conv_dim == 24576
    shapes = jax.eval_shape(
        lambda k: so.draw_params(k, dataclasses.replace(cfg, layer_kinds=cfg.layer_kinds[:4])),
        jax.random.key(0),
    )
    count = lambda t: sum(x.size for x in jax.tree.leaves(t))  # noqa: E731
    gqa, kda = shapes["layers"][0], shapes["layers"][1]
    expert = 3 * 4096 * 1280
    per_layer = lambda p: count(p) - 320 * expert  # noqa: E731: beside its routed experts
    assert round((per_layer(kda) - expert - 4096 * 320 - 320) / 1e5) == 1377  # the KDA mixer: 137.7 M
    assert round((per_layer(gqa) - expert - 4096 * 320 - 320) / 1e5) == 1091  # the GQA mixer: 109.1 M
    total = 12 * count(gqa) + 36 * count(kda) + 2 * 196608 * 4096 + 4096
    active = total - 48 * (320 - 8) * expert
    assert round(total / 1e7) == 25029 and round(active / 1e7) == 1474


def test_pool_parts_and_what_the_paged_programs_refuse(tiny):
    cfg, _ = tiny
    pool = paged.init_block_pool(cfg, 9, 16, 6)
    H, d = cfg.kda_heads, cfg.kda_head_dim
    assert pool["k"].shape == pool["v"].shape == (2, 9, cfg.n_kv_head, 16, cfg.head_dim)
    assert pool["state"].shape == (3, 7, H, d, d) and pool["state"].dtype == jnp.float32
    assert pool["conv"].shape == (3, 7, cfg.conv_kernel - 1, cfg.conv_dim)
    assert paged.init_block_pool(cfg, 9, 16)["state"].shape[1] == cfg.state_slots + 1
    assert paged.cache(cfg).slot_state and not paged.cache(cfg).hooks
    assert paged.cache(cfg).retention == (None,)  # one layer kind: everything is kept
    assert not paged.cache(LlamaConfig.tiny()).slot_state
    with pytest.raises(ValueError, match="recurrent state"):
        paged.paged_verify(None, jnp.zeros((1, 2), jnp.int32), None, None, pool, cfg, block_size=16)


def test_one_kda_implementation_serves_both_families_and_reads_betas_range(tiny):
    """``solar_open2`` and ``kimi_linear`` call ``models/kda.py``'s mixer;
    ``kda_neg_eigval`` doubles ``beta`` and changes nothing else, and Kimi
    Linear's stays a sigmoid."""
    assert so.kda_prefill is kl.kda_prefill is kda.kda_prefill
    assert so.kda_decode is kl.kda_decode is kda.kda_decode
    cfg, params = tiny
    p = params["layers"][1]
    h = jax.random.normal(jax.random.key(1), (8, cfg.d_model))
    mixed = jax.random.normal(jax.random.key(2), (8, cfg.conv_dim))
    wide = kda._kda_inputs(h, mixed, p, cfg)
    unit = kda._kda_inputs(h, mixed, p, dataclasses.replace(cfg, kda_neg_eigval=False))
    for a, b in zip(wide[:4], unit[:4]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(wide[4], 2.0 * unit[4], rtol=1e-6)
    assert float(wide[4].max()) > 1.0 and float(wide[4].max()) < 2.0
    assert not kl.KimiLinearConfig.tiny().kda_neg_eigval and not kl.KimiLinearConfig().kda_neg_eigval


def _run_prefill(prefill, params, toks, n, table, pool, slot, chunk=None, width=None):
    """Prefill ``toks[:n]`` into ``slot``: whole, in a bucket of ``width``, or
    in chunks of ``chunk``; returns ``(pool, the last logits)``."""
    if chunk is None:
        t = np.zeros((1, width), np.int32)
        t[0, :n] = toks[:n]
        pool, logits, _ = prefill(
            params, jnp.asarray(t), jnp.asarray(n), jnp.asarray(0), jnp.asarray(table), pool,
            slot=jnp.asarray(slot),
        )
        return pool, logits
    for start in range(0, n, chunk):
        m = min(chunk, n - start)
        t = np.zeros((1, chunk), np.int32)
        t[0, :m] = toks[start : start + m]
        pool, logits, _ = prefill(
            params, jnp.asarray(t), jnp.asarray(m), jnp.asarray(start), jnp.asarray(table), pool,
            slot=jnp.asarray(slot),
        )
    return pool, logits


@pytest.mark.parametrize("chunk", [None, 16], ids=["whole", "chunks_of_16"])
@pytest.mark.parametrize("interpret", [False, True], ids=["gather", "kernel_interpreted"])
def test_paged_prefill_and_decode_are_the_reference_forward(tiny, interpret, chunk):
    """Two prompts into two slots and scattered tables, whole or in chunks
    that carry the state and the tail, then three decode steps with a free
    and a not-live slot beside them: logits against the reference's full
    forward, the rows of keys and values where they lie in the pool, and each
    slot's state and tail after the prompt and after the last step. The arm
    is the decode step's kernels' and the KDA layers' prefill scan's alike:
    plain, or the kernel in the Pallas interpreter."""
    cfg, params = tiny
    c = ref_config(cfg)
    bs, W, B, K = 16, 8, 4, 3
    rng = np.random.default_rng(0)
    lens, slots = [50, 23], [2, 0]
    toks = rng.integers(0, cfg.vocab_size, size=(2, max(lens) + K)).astype(np.int32)
    wants = [
        ref.forward(params, jnp.asarray(toks[i, : n + K]), c, inner=True, state_at=(n, n + K))
        for i, n in enumerate(lens)
    ]
    prefill = jax.jit(functools.partial(paged.paged_prefill, cfg=cfg, block_size=bs, interpret=interpret))
    decode = jax.jit(functools.partial(paged.paged_decode, cfg=cfg, block_size=bs, interpret=interpret))
    pool = paged.init_block_pool(cfg, 20, bs, B)
    # whatever was in the slots before must not matter: a second request starts from zero
    pool["state"] = pool["state"] + 3.0
    pool["conv"] = pool["conv"] + 1.0
    free = list(rng.permutation(np.arange(1, 20)))
    tables = np.zeros((B, W), np.int32)
    for i, n in enumerate(lens):
        need = -(-(n + K) // bs)
        tables[slots[i], :need] = [free.pop() for _ in range(need)]
        pool, logits = _run_prefill(
            prefill, params, toks[i], n, tables[slots[i]], pool, slots[i], chunk, 64 if n > 32 else 32
        )
        want, inner = wants[i]
        np.testing.assert_allclose(logits, want[n - 1], rtol=2e-3, atol=2e-5)
        np.testing.assert_allclose(pool["state"][:, slots[i]], inner["state"][0], rtol=2e-3, atol=2e-5)
        np.testing.assert_allclose(pool["conv"][:, slots[i]], inner["conv"][0], rtol=2e-3, atol=2e-5)
    live = np.zeros(B, bool)
    live[slots] = True
    others = np.asarray(pool["state"][:, [1, 3, 4]])
    for k in range(K):
        last, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
        for i, n in enumerate(lens):
            last[slots[i]], pos[slots[i]] = toks[i, n + k], n + k
        pool, logits, counts = decode(
            params, jnp.asarray(last), jnp.asarray(pos), jnp.asarray(tables), pool,
            live=jnp.asarray(live),
        )
        for i, n in enumerate(lens):
            np.testing.assert_allclose(logits[slots[i]], wants[i][0][n + k], rtol=2e-3, atol=2e-5)
        assert counts.shape == (cfg.n_moe_layers, 2)
        assert counts[:, 0].tolist() == [2 * cfg.experts_per_token] * cfg.n_moe_layers
    np.testing.assert_array_equal(pool["state"][:, [1, 3, 4]], others)  # not live: left alone
    for i, n in enumerate(lens):
        _, inner = wants[i]
        np.testing.assert_allclose(pool["state"][:, slots[i]], inner["state"][1], rtol=2e-3, atol=2e-5)
        np.testing.assert_allclose(pool["conv"][:, slots[i]], inner["conv"][1], rtol=2e-3, atol=2e-5)
        # the rows of keys and values as they lie in the pool, in the order of the positions
        k_, v_ = (
            np.asarray(pool[x][:, tables[slots[i]]]).transpose(0, 1, 3, 2, 4).reshape(2, W * bs, -1)
            for x in ("k", "v")
        )
        rows = np.concatenate([k_, v_], axis=-1)[:, : n + K]
        np.testing.assert_allclose(rows, inner["kv"], rtol=2e-3, atol=2e-5)


def test_a_chunk_sees_the_rows_and_the_state_the_chunks_before_it_left(tiny):
    """A second chunk from a zeroed state, from a zeroed tail, or over a table
    that lost the first chunk's block gives other logits: the three things a
    later chunk reads."""
    cfg, params = tiny
    bs = 16
    toks = np.random.default_rng(1).integers(0, cfg.vocab_size, size=40).astype(np.int32)
    prefill = jax.jit(functools.partial(paged.paged_prefill, cfg=cfg, block_size=bs))
    table = np.arange(1, 5, dtype=np.int32)
    first, _ = _run_prefill(prefill, params, toks, 32, table, paged.init_block_pool(cfg, 6, bs, 2), 1, chunk=32)

    def second(pool, table=table):
        t = jnp.zeros((1, bs), jnp.int32).at[0, :8].set(toks[32:40])  # a bucket of one whole block
        return prefill(params, t, jnp.asarray(8), jnp.asarray(32), jnp.asarray(table), pool, slot=jnp.asarray(1))[1]

    right = second(first)
    whole = _run_prefill(prefill, params, toks, 40, table, paged.init_block_pool(cfg, 6, bs, 2), 0, width=64)[1]
    np.testing.assert_allclose(right, whole, rtol=2e-3, atol=2e-5)
    far = lambda got: float(jnp.abs(got - right).max()) > 1e-3  # noqa: E731
    assert far(second({**first, "state": jnp.zeros_like(first["state"])}))
    assert far(second({**first, "conv": jnp.zeros_like(first["conv"])}))
    assert far(second(first, table=np.array([5, 2, 3, 4], np.int32)))


def test_the_gqa_layer_is_gated_and_attends_without_rotation(tiny):
    """One GQA layer alone against the reference's, and against the reference
    with its controls' departures: rotating ``q`` and ``k`` or leaving the gate
    out gives another result."""
    cfg, params = tiny
    p, c = params["layers"][0], ref_config(cfg)
    a = jax.random.normal(jax.random.key(3), (24, cfg.d_model))
    mm = lambda x, w: x @ w.astype(jnp.float32)  # noqa: E731
    want, kv = ref.gqa(a, p, c, mm, lambda x: x)
    q, k, v, g = so._qkvg(a, p, cfg)
    pool = paged.init_block_pool(dataclasses.replace(cfg, layer_kinds=(so.GQA,)), 3, 16, 0)
    pos = jnp.arange(24)
    table = jnp.asarray([1, 2])
    pk = paged._write(pool["k"], 0, table[pos // 16], pos % 16, k)
    pv = paged._write(pool["v"], 0, table[pos // 16], pos % 16, v)
    o = paged.prefill_attention(q, pk, pv, 0, table, pos, jnp.asarray(24), block_size=16)
    np.testing.assert_allclose(so._gated_out(o, g, p, cfg), want, rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(
        jnp.concatenate([k.reshape(24, -1), v.reshape(24, -1)], -1), kv, rtol=1e-5, atol=1e-6)
    for wrong in ("rotated", "ungated"):
        other, _ = ref.gqa(a, p, c, mm, lambda x: x, wrong)
        assert float(jnp.abs(other - want).max()) > 10 * float(jnp.abs(so._gated_out(o, g, p, cfg) - want).max())


def test_the_eight_shares_add_up_to_the_uncut_layer(tiny):
    """Eight chips hold one of the eight experts each (the deployment's eight
    shares of 40 of 320, at the tiny size). Their routed parts, with the shared
    expert (which every chip computes alike) counted once, are what the uncut
    reference layer gives."""
    cfg, params = tiny
    p = params["layers"][1]
    h = jax.random.normal(jax.random.key(6), (40, cfg.d_model))
    mm = lambda a, w: a @ w  # noqa: E731
    whole, _ = ref.experts(h, p, ref_config(cfg), mm)
    shared = (jax.nn.silu(h @ p["s_gate"]) * (h @ p["s_up"])) @ p["s_down"]
    routed, here = 0.0, 0
    for chip in range(8):
        share = dataclasses.replace(cfg, experts_held=1, expert_offset=chip)
        held = {k: v[chip : chip + 1] if k.startswith("e_") else v for k, v in p.items()}
        y, counts, _ = so.latent_moe.moe_ffn(h, held, share)
        routed += y - shared
        here += int(counts[0])
        one, _ = ref.experts(h, held, ref_config(share), mm, shared=False)
        np.testing.assert_allclose(y - shared, one, rtol=2e-4, atol=2e-6)  # a share alone, too
    assert here == 40 * cfg.experts_per_token  # every pick landed on exactly one chip
    np.testing.assert_allclose(routed + shared, whole, rtol=2e-4, atol=5e-6)


def test_the_routers_are_balanced_over_the_text_that_is_served():
    """``init_params`` balances the selection bias over printable bytes, the
    ids the clients' prompts are made of: over such text each expert of each
    layer gets about its share of the picks."""
    cfg = so.SolarOpen2Config.tiny(layer_kinds=(so.GQA, so.KDA))
    drawn = so.draw_params(jax.random.split(jax.random.key(2))[0], cfg)
    assert all(float(jnp.abs(p["router_bias"]).max()) == 0 for p in drawn["layers"])
    params = so.init_params(jax.random.key(2), cfg)
    assert all(float(jnp.abs(p["router_bias"]).max()) > 0 for p in params["layers"])
    toks = jax.random.randint(jax.random.key(4), (1, 128), 32, 127)
    *_, picks = so.paged_prefill(
        params, toks, jnp.asarray(128), jnp.asarray(0), jnp.arange(1, 9), so.init_pool(cfg, 9, 16, 0),
        cfg, block_size=16, with_picks=True,
    )
    loads = np.stack([np.bincount(np.asarray(l).reshape(-1), minlength=cfg.n_experts) for l in picks])
    assert loads.sum() == cfg.n_moe_layers * 128 * cfg.experts_per_token
    assert loads.min() > 0.4 * loads.mean() and loads.max() < 1.6 * loads.mean(), loads


def test_silent_ids_are_zero_columns_of_the_head():
    cfg = so.SolarOpen2Config.tiny(layer_kinds=(so.GQA, so.KDA), silent_ids=(257,))
    head = so.draw_params(jax.random.key(0), cfg)["lm_head"]
    assert float(jnp.abs(head[:, 257]).max()) == 0 and float(jnp.abs(head[:, 256]).max()) > 0


def test_span_fields_count_the_rows_a_gqa_layer_needs_and_reads():
    cfg = so.SolarOpen2Config.tiny()
    counts = np.arange(2 * cfg.n_moe_layers + 3)  # padding behind the counters is ignored
    plain = so.span_fields(cfg, counts, tokens=7, slots=1)
    assert plain["state_slots"] == 1 and plain["picks"] == 7 * 2 * 5 and "kv_rows_live" not in plain
    assert plain["picks_here"] == 0 + 2 + 4 + 6 + 8 and plain["experts_touched"] == 1 + 3 + 5 + 7 + 9
    step = so.span_fields(cfg, counts, 3, 3, decode=(np.array([15, 16, 40]), 6 * 16))
    assert step["kv_rows_live"] == 16 + 17 + 41 and step["kv_rows_read"] == 96 and step["state_slots"] == 3


def test_the_reference_gives_the_same_whether_its_heads_go_one_or_all_at_a_time(tiny, monkeypatch):
    """The reference runs the delta rule ``HEADS`` heads at a time so that a
    14k-token sequence fits beside the weights; the runs' columns, states and
    tails come back in the heads' order."""
    cfg, params = tiny
    c = ref_config(cfg)
    toks = jnp.asarray(np.random.default_rng(9).integers(0, cfg.vocab_size, size=21), jnp.int32)
    whole = ref.forward(params, toks, c, inner=True, state_at=(9, 21))
    monkeypatch.setattr(ref, "HEADS", 1)
    by_one = ref.forward(params, toks, c, inner=True, state_at=(9, 21))
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(by_one)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    assert whole[1]["state"].shape == (2, 3, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim)
    assert whole[1]["conv"].shape == (2, 3, cfg.conv_kernel - 1, cfg.conv_dim)
