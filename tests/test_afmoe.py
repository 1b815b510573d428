"""The ``afmoe`` family's paged programs against the plain reference, float32
at a tiny size on the CPU: a window of 8 over blocks of 4, prefilled in chunks
of 8, contexts of 40 and more. The engine over them is ``test_llm_afmoe.py``.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import afmoe_ref as ref  # noqa: E402
from ray_tpu.models import afmoe, latent_moe, paged  # noqa: E402

pytestmark = pytest.mark.timeout(300)
BLOCK, CHUNK, WIDTH = 4, 8, 16  # a table of 16 blocks: 64 positions
TOL = dict(rtol=2e-4, atol=2e-6)


def ref_config(cfg: afmoe.AfmoeConfig) -> dict:
    """The reference's dictionary of published keys for ``cfg``."""
    return dict(
        hidden_size=cfg.d_model, vocab_size=cfg.vocab_size, num_attention_heads=cfg.n_head,
        num_key_value_heads=cfg.n_kv_head, head_dim=cfg.head_dim, layer_types=list(cfg.layer_types),
        num_dense_layers=cfg.n_dense, sliding_window=cfg.sliding_window, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_eps, num_experts=cfg.experts_held, expert_offset=cfg.expert_offset,
        num_experts_per_tok=cfg.experts_per_token, route_norm=cfg.renormalize,
        route_scale=cfg.routed_scaling, mup_enabled=cfg.mup,
        published=dict(num_experts=cfg.n_experts),
    )


@pytest.fixture(scope="module")
def tiny():
    cfg = afmoe.AfmoeConfig.tiny()
    return cfg, afmoe.init_params(jax.random.key(0), cfg)


def _tokens(n, seed=1):
    return jax.random.randint(jax.random.key(seed), (n,), 0, 512)


def _prefill_in_chunks(cfg, params, toks, upto, table, pool):
    """``toks[:upto]`` through ``paged_prefill`` a chunk of 8 at a time; the
    logits after each chunk's last token."""
    out = []
    for start in range(0, upto, CHUNK):
        pool, logits, _ = afmoe.paged_prefill(
            params, toks[start : start + CHUNK][None], jnp.int32(CHUNK), jnp.int32(start),
            table, pool, cfg, block_size=BLOCK,
        )
        out.append(logits)
    return pool, jnp.stack(out)


def _window_table(table, position, window):
    """``table`` with every block wholly behind the window of a query at
    ``position`` pointed at the scratch block, as the engine leaves it."""
    dead = max(position - window + 1, 0) // BLOCK
    return table.at[:dead].set(0)


@pytest.mark.parametrize("interpret", [False, True], ids=["gather", "kernel interpreted"])
def test_prefill_in_chunks_then_decode_through_both_tables_is_the_reference(tiny, interpret):
    """Forty tokens in five chunks, then five decode steps, the window layers
    under a table of their own whose blocks behind the window point at the
    scratch block (which is poisoned): the logits are the reference's full
    forward at every compared position."""
    cfg, params = tiny
    toks = _tokens(45)
    want = ref.forward(params, toks, ref_config(cfg))
    pool = afmoe.init_pool(cfg, WIDTH + 1, BLOCK, window_blocks=WIDTH + 1)
    full = jnp.arange(1, WIDTH + 1, dtype=jnp.int32)
    got = []
    for start in range(0, 40, CHUNK):
        tables = jnp.stack([full, _window_table(full, start, cfg.sliding_window)])
        pool, logits, counts = afmoe.paged_prefill(
            params, toks[start : start + CHUNK][None], jnp.int32(CHUNK), jnp.int32(start),
            tables, pool, cfg, block_size=BLOCK,
        )
        got.append(logits)
    assert counts.shape == (cfg.n_moe_layers, 2)
    np.testing.assert_allclose(jnp.stack(got), want[CHUNK - 1 : 40 : CHUNK], **TOL)
    pool = jax.tree.map(lambda x: x.at[:, 0].set(jnp.nan) if interpret else x, pool)
    for i in range(40, 45):
        tables = jnp.stack([full, _window_table(full, i, cfg.sliding_window)])[None]
        pool, logits, _ = afmoe.paged_decode(
            params, toks[i][None], jnp.asarray([i]), tables, pool, cfg, block_size=BLOCK,
            interpret=interpret,
        )
        np.testing.assert_allclose(logits[0], want[i], **TOL)


def test_a_padded_last_chunk_is_the_unpadded_one(tiny):
    """A chunk of 5 tokens in the bucket of 8: the logits of its last real
    token, whatever the padded rows hold."""
    cfg, params = tiny
    toks = _tokens(37, seed=2)
    want = ref.forward(params, toks, ref_config(cfg))
    table = jnp.arange(1, WIDTH + 1, dtype=jnp.int32)
    pool, _ = _prefill_in_chunks(
        cfg, params, toks, 32, table, afmoe.init_pool(cfg, WIDTH + 1, BLOCK, window_blocks=WIDTH + 1)
    )
    last = jnp.zeros((1, CHUNK), jnp.int32).at[0, :5].set(toks[32:])
    _, logits, _ = afmoe.paged_prefill(
        params, last, jnp.int32(5), jnp.int32(32), table, pool, cfg, block_size=BLOCK
    )
    np.testing.assert_allclose(logits, want[36], **TOL)


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Eight chips hold an eighth of the experts each and all the shared
    expert: their routed parts and the shared expert once are the reference's
    layer with every expert held."""
    whole = afmoe.AfmoeConfig.tiny(n_experts=16, experts_held=16)
    p = afmoe.draw_params(jax.random.key(3), whole)["layers"][1]
    p = {**p, "router_bias": 0.1 * jax.random.normal(jax.random.key(4), (16,))}
    m = jax.random.normal(jax.random.key(5), (24, whole.d_model))
    mm = lambda a, w: a @ w.astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want, picks = ref.experts(m, p, ref_config(whole), mm)
        shared = ref.swiglu(m, p["s_gate"], p["s_up"], p["s_down"], mm)
    total = -7 * shared  # the shared expert is in every share
    for n in range(8):
        cut = afmoe.AfmoeConfig.tiny(n_experts=16, experts_held=2, expert_offset=2 * n)
        share = {**p, **{k: p[k][2 * n : 2 * n + 2] for k in ("e_gate", "e_up", "e_down")}}
        y, counts, idx = latent_moe.moe_ffn(m, share, cut, None)
        np.testing.assert_array_equal(np.sort(idx, -1), np.sort(picks, -1))
        total = total + y
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def test_the_programs_router_at_one_group_is_the_references(tiny):
    cfg, params = tiny
    p = {**params["layers"][1], "router_bias": 0.2 * jax.random.normal(jax.random.key(6), (8,))}
    m = jax.random.normal(jax.random.key(7), (64, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want_idx, want_w = ref.route(m, p, ref_config(cfg), lambda a, w: a @ w)
    idx, w = latent_moe.route(m, p, cfg)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(w, want_w, rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(w, -1), cfg.routed_scaling, rtol=1e-5)


@pytest.mark.parametrize("wrong, off", [
    ("rope_everywhere", "a full layer rotated"), ("no_window", "a sliding layer that sees everything"),
    ("ungated", "no sigmoid(g)"),
])
def test_full_layers_are_unrotated_sliding_ones_rotated_and_windowed_each_against_a_control(tiny, wrong, off):
    """The program is the reference and not the reference computed wrongly:
    with full layers rotated too, with sliding layers attending everything,
    or without the gate, the reference is far from the program."""
    cfg, params = tiny
    toks = _tokens(40, seed=8)
    table = jnp.arange(1, WIDTH + 1, dtype=jnp.int32)
    pool = afmoe.init_pool(cfg, WIDTH + 1, BLOCK, window_blocks=WIDTH + 1)
    _, got = _prefill_in_chunks(cfg, params, toks, 40, table, pool)
    c = ref_config(cfg)
    at = slice(CHUNK - 1, 40, CHUNK)
    np.testing.assert_allclose(got, ref.forward(params, toks, c)[at], **TOL)
    control = ref.forward(params, toks, c, wrong=wrong)[at]
    assert float(jnp.linalg.norm(control - got) / jnp.linalg.norm(got)) > 0.05, off


def test_a_sliding_layer_alone_rotates_and_a_full_layer_alone_does_not():
    """One layer of each kind by itself: shifting every position by a
    constant leaves a sliding layer's logits as they were (rotation is
    relative) and so does it a full layer's (it knows no position), while a
    full layer rotated by the control depends on nothing absolute either:
    the kinds differ in whether the *relative* order within the window
    matters, which the reference's control shows."""
    for kinds in ((afmoe.SLIDING, afmoe.SLIDING), (afmoe.FULL, afmoe.FULL)):
        cfg = afmoe.AfmoeConfig.tiny(layer_types=kinds)
        params = afmoe.init_params(jax.random.key(9), cfg)
        toks = _tokens(16, seed=10)
        c = ref_config(cfg)
        want = ref.forward(params, toks, c)
        table = jnp.arange(1, WIDTH + 1, dtype=jnp.int32)
        pool = afmoe.init_pool(cfg, WIDTH + 1, BLOCK, window_blocks=WIDTH + 1)
        _, got = _prefill_in_chunks(cfg, params, toks, 16, table, pool)
        np.testing.assert_allclose(got, want[CHUNK - 1 : 16 : CHUNK], **TOL)
        rotated = ref.forward(params, toks, c, wrong="rope_everywhere")
        same = np.allclose(rotated, want, rtol=1e-5)
        assert same == (kinds[0] == afmoe.SLIDING)  # the control changes full layers only


def test_the_pool_has_a_part_a_kind_and_the_window_part_is_counted_not_set():
    cfg = afmoe.AfmoeConfig.tiny()
    pool = afmoe.init_pool(cfg, 33, BLOCK, slots=3)
    assert pool["full"]["k"].shape == (1, 33, 2, BLOCK, 16)
    # ceil((8 + 8) / 4) + 1 = 5 blocks a slot, and the scratch block
    assert pool["window"]["k"].shape == (3, 3 * 5 + 1, 2, BLOCK, 16)
    assert afmoe.cache(cfg) == paged.Cache(
        retention=(None, 8), per_head=True, hooks=False, prefill_in_place=True)


def _loads(cfg, params, texts):
    """Each expert's share of the picks of ``texts`` prefilled one by one, an
    expert layer a row."""
    n = texts.shape[1]
    table = jnp.arange(1, n // BLOCK + 1, dtype=jnp.int32)
    pool = afmoe.init_pool(cfg, n // BLOCK + 1, BLOCK, window_blocks=n // BLOCK + 1)
    picks = [
        afmoe.paged_prefill(
            params, t[None], jnp.int32(n), jnp.int32(0), table, pool, cfg, block_size=BLOCK, with_picks=True,
        )[-1]
        for t in texts
    ]
    return np.mean(jax.nn.one_hot(jnp.stack(picks), cfg.n_experts), axis=(0, 2, 3))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_selection_bias_is_balanced_over_the_text_that_is_served(seed):
    """``init_params`` balances over sequences of printable bytes: over eight
    such sequences every expert of every layer takes within half of its eighth
    of the picks, and the weights as drawn leave some expert further off."""
    cfg = afmoe.AfmoeConfig.tiny()
    key, _ = jax.random.split(jax.random.key(seed))
    texts = jax.random.randint(jax.random.key(100 + seed), (8, 256), *afmoe._BALANCE_TEXT_IDS)
    share = 1.0 / cfg.n_experts
    off = lambda params: float(np.abs(_loads(cfg, params, texts) - share).max()) / share  # noqa: E731
    balanced = off(afmoe.init_params(jax.random.key(seed), cfg))
    assert balanced < 0.5
    assert off(afmoe.draw_params(key, cfg)) > max(0.5, balanced)


def test_a_silent_id_is_never_the_greedy_choice():
    """``silent_ids`` leaves those columns of the head at zero and every other
    weight as it was: their logit is 0 whatever the hidden state, under the
    largest of a vocabulary's others."""
    cfg = afmoe.AfmoeConfig.tiny()
    quiet = dataclasses.replace(cfg, silent_ids=(7, 257))
    plain, params = afmoe.draw_params(jax.random.key(3), cfg), afmoe.draw_params(jax.random.key(3), quiet)
    head = np.asarray(params["lm_head"])
    assert not head[:, [7, 257]].any() and np.abs(head).sum(axis=0).astype(bool).sum() == cfg.vocab_size - 2
    keep = np.ones(cfg.vocab_size, bool)
    keep[[7, 257]] = False
    np.testing.assert_array_equal(head[:, keep], np.asarray(plain["lm_head"])[:, keep])
    np.testing.assert_array_equal(np.asarray(params["wte"]), np.asarray(plain["wte"]))
    table = jnp.arange(1, 64 // BLOCK + 1, dtype=jnp.int32)
    pool = afmoe.init_pool(cfg, 64 // BLOCK + 1, BLOCK, window_blocks=64 // BLOCK + 1)
    for n in range(4):
        toks = jax.random.randint(jax.random.key(4 + n), (1, 64), 0, cfg.vocab_size)
        logits = np.asarray(afmoe.paged_prefill(
            params, toks, jnp.int32(64), jnp.int32(0), table, pool, quiet, block_size=BLOCK,
        )[1])
        assert not logits[[7, 257]].any() and logits.argmax() not in (7, 257) and logits.max() > 0
