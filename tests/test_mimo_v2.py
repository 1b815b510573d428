"""The ``mimo_v2`` family's paged programs against the plain reference, float32
at a tiny size on the CPU with the published ratios: keys 1.5 times as wide as
values, a third of a key rotated, one key/value head in a full layer to two in
a window layer, a window of 6 over blocks of 4 (no multiple of a block, shorter
than the chunk of 8), a learned sink in the window layers, no shared expert.
The decode kernel with a sink and unequal widths against the gather, in the
Pallas interpreter. The engine over the programs is ``test_llm_mimo_v2.py``.

Tolerances: float32 on both sides, so 2e-4 relative (the reference multiplies
at ``highest`` precision; the program's running softmax sums in another order),
as ``test_afmoe.py`` holds its family to.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import mimo_v2_ref as ref  # noqa: E402
from ray_tpu.models import latent_moe, mimo_v2, paged  # noqa: E402
from ray_tpu.ops.paged_attention import paged_decode_attention  # noqa: E402

pytestmark = pytest.mark.timeout(300)
BLOCK, CHUNK, WIDTH = 4, 8, 16  # a table of 16 blocks: 64 positions
TOL = dict(rtol=2e-4, atol=2e-5)


def ref_config(cfg: mimo_v2.MimoV2Config) -> dict:
    """The reference's dictionary of published keys for ``cfg``."""
    return dict(
        hidden_size=cfg.d_model, vocab_size=cfg.vocab_size, num_attention_heads=cfg.n_head,
        num_key_value_heads=cfg.n_kv_head, swa_num_key_value_heads=cfg.swa_n_kv_head,
        head_dim=cfg.head_dim, swa_head_dim=cfg.head_dim, v_head_dim=cfg.v_head_dim,
        swa_v_head_dim=cfg.v_head_dim, partial_rotary_factor=(cfg.rotary_dim + 0.1) / cfg.head_dim,
        rope_theta=cfg.rope_theta, swa_rope_theta=cfg.swa_rope_theta,
        attention_value_scale=cfg.value_scale, sliding_window=cfg.sliding_window,
        add_swa_attention_sink_bias=cfg.swa_sink, add_full_attention_sink_bias=cfg.full_sink,
        hybrid_layer_pattern=list(cfg.layer_pattern), moe_layer_freq=list(cfg.moe_layers),
        layernorm_epsilon=cfg.rms_eps, n_routed_experts=cfg.experts_held,
        expert_offset=cfg.expert_offset, num_experts_per_tok=cfg.experts_per_token,
        norm_topk_prob=cfg.renormalize, routed_scaling_factor=None, n_shared_experts=None,
        scoring_func="sigmoid", topk_method="noaux_tc", n_group=1, topk_group=1,
        published=dict(n_routed_experts=cfg.n_experts),
    )


@pytest.fixture(scope="module")
def tiny():
    cfg = mimo_v2.MimoV2Config.tiny()
    return cfg, mimo_v2.init_params(jax.random.key(0), cfg)


def _tokens(n, seed=1):
    return jax.random.randint(jax.random.key(seed), (n,), 0, 512)


def _pool(cfg):
    return mimo_v2.init_pool(cfg, WIDTH + 1, BLOCK, window_blocks=WIDTH + 1)


def _prefill_in_chunks(cfg, params, toks, upto, table, pool):
    """``toks[:upto]`` through ``paged_prefill`` a chunk of 8 at a time; the
    logits after each chunk's last token."""
    out = []
    for start in range(0, upto, CHUNK):
        pool, logits, _ = mimo_v2.paged_prefill(
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


def test_a_whole_prompt_is_the_reference_and_its_sinks_take_their_share(tiny):
    """Forty tokens in one program: the last token's logits, the keys and
    values of both parts as they lie in the pool (scaled values, keys rotated
    in their first lanes), and the reference's reading of what the sinks take
    of a whole window's probability: between a tenth and a half."""
    cfg, params = tiny
    toks = _tokens(40)
    want, inner = ref.forward(params, toks, ref_config(cfg), inner=True)
    table = jnp.arange(1, WIDTH + 1, dtype=jnp.int32)
    pool, logits, counts = mimo_v2.paged_prefill(
        params, toks[None], jnp.int32(40), jnp.int32(0), table, _pool(cfg), cfg, block_size=BLOCK,
    )
    np.testing.assert_allclose(logits, want[39], **TOL)
    assert counts.shape == (cfg.n_moe_layers, 2)
    for part, heads in (("full", 1), ("window", 2)):
        k, v = (pool[part][x][:, 1:11].transpose(0, 1, 3, 2, 4) for x in ("k", "v"))  # [L, blocks, block, KH, D]
        assert k.shape[3] == v.shape[3] == heads and (k.shape[-1], v.shape[-1]) == (24, 16)
        rows = jnp.concatenate([k.reshape(k.shape[0], 40, -1), v.reshape(v.shape[0], 40, -1)], -1)
        np.testing.assert_allclose(rows, inner["kv"][part], **TOL)
    share = np.asarray(inner["sink_share"])
    assert share.shape == (3,) and (share > 0.1).all() and (share < 0.5).all(), share


@pytest.mark.parametrize("interpret", [False, True], ids=["gather", "kernel interpreted"])
def test_prefill_in_chunks_then_decode_through_both_tables_is_the_reference(tiny, interpret):
    """Forty tokens in five chunks (each longer than the window), then five
    decode steps, the window layers under a table of their own whose blocks
    behind the window point at the scratch block (which is poisoned): the
    logits are the reference's full forward at every compared position."""
    cfg, params = tiny
    toks = _tokens(45)
    want = ref.forward(params, toks, ref_config(cfg))
    pool = _pool(cfg)
    full = jnp.arange(1, WIDTH + 1, dtype=jnp.int32)
    got = []
    for start in range(0, 40, CHUNK):
        tables = jnp.stack([full, _window_table(full, start, cfg.sliding_window)])
        pool, logits, _ = mimo_v2.paged_prefill(
            params, toks[start : start + CHUNK][None], jnp.int32(CHUNK), jnp.int32(start),
            tables, pool, cfg, block_size=BLOCK,
        )
        got.append(logits)
    np.testing.assert_allclose(jnp.stack(got), want[CHUNK - 1 : 40 : CHUNK], **TOL)
    pool = jax.tree.map(lambda x: x.at[:, 0].set(jnp.nan) if interpret else x, pool)
    for i in range(40, 45):
        tables = jnp.stack([full, _window_table(full, i, cfg.sliding_window)])[None]
        pool, logits, _ = mimo_v2.paged_decode(
            params, toks[i][None], jnp.asarray([i]), tables, pool, cfg, block_size=BLOCK,
            interpret=interpret,
        )
        np.testing.assert_allclose(logits[0], want[i], **TOL)


def test_a_window_block_given_back_and_taken_again_holds_the_new_rows(tiny):
    """The window kind's table turns over two physical blocks: once a block
    is wholly behind the window it is pointed at the scratch block and the
    next one to be written takes its id, as ``WindowBlocks`` does it. Decode
    over 24 positions is the reference's, though every window block but the
    newest two has been overwritten by a later position's rows."""
    cfg, params = tiny
    toks = _tokens(30, seed=12)
    want = ref.forward(params, toks, ref_config(cfg))
    full = jnp.arange(1, WIDTH + 1, dtype=jnp.int32)
    pool = mimo_v2.init_pool(cfg, WIDTH + 1, BLOCK, window_blocks=4)  # scratch and three to turn over
    window, free = np.zeros(WIDTH, np.int32), [3, 2, 1]
    tables = lambda: jnp.stack([full, jnp.asarray(window)])  # noqa: E731

    def advance(first_query, upto):
        dead = max(first_query - cfg.sliding_window + 1, 0) // BLOCK
        for i in range(dead):
            if window[i]:
                free.append(int(window[i]))
                window[i] = 0
        for i in range(-(-upto // BLOCK)):
            if i >= dead and not window[i]:
                window[i] = free.pop()

    advance(0, CHUNK)
    pool, logits, _ = mimo_v2.paged_prefill(
        params, toks[:CHUNK][None], jnp.int32(CHUNK), jnp.int32(0), tables(), pool, cfg, block_size=BLOCK,
    )
    np.testing.assert_allclose(logits, want[CHUNK - 1], **TOL)
    taken_again = set()
    for i in range(CHUNK, 30):
        before = set(window.tolist())
        advance(i, i + 1)
        taken_again |= set(window.tolist()) - before
        pool, logits, _ = mimo_v2.paged_decode(
            params, toks[i][None], jnp.asarray([i]), tables()[None], pool, cfg, block_size=BLOCK,
        )
        np.testing.assert_allclose(logits[0], want[i], **TOL)
    assert taken_again == {1, 2, 3}  # every block came back and was written again


def test_a_padded_last_chunk_is_the_unpadded_one(tiny):
    """A chunk of 5 tokens in the bucket of 8: the logits of its last real
    token, whatever the padded rows hold."""
    cfg, params = tiny
    toks = _tokens(37, seed=2)
    want = ref.forward(params, toks, ref_config(cfg))
    table = jnp.arange(1, WIDTH + 1, dtype=jnp.int32)
    pool, _ = _prefill_in_chunks(cfg, params, toks, 32, table, _pool(cfg))
    last = jnp.zeros((1, CHUNK), jnp.int32).at[0, :5].set(toks[32:])
    _, logits, _ = mimo_v2.paged_prefill(
        params, last, jnp.int32(5), jnp.int32(32), table, pool, cfg, block_size=BLOCK
    )
    np.testing.assert_allclose(logits, want[36], **TOL)


def test_keys_of_192_in_rows_of_256_beside_values_of_128_through_the_interpreted_kernel():
    """The published head widths (hidden state and everything else tiny): the
    key pool's rows are 256 lanes, zeros behind the 192 of a key, the values'
    128; prefill and the interpreted kernel over them are the reference's."""
    cfg = mimo_v2.MimoV2Config.tiny(
        layer_pattern=(mimo_v2.FULL, mimo_v2.WINDOW), head_dim=192, v_head_dim=128, rotary_dim=64,
    )
    assert cfg.key_lanes == 256 and [k.key_lanes for k in mimo_v2.attention_kinds(cfg)] == [256, 256]
    params = mimo_v2.init_params(jax.random.key(2), cfg)
    toks = _tokens(20, seed=3)
    want = ref.forward(params, toks, ref_config(cfg))
    table = jnp.arange(1, WIDTH + 1, dtype=jnp.int32)
    pool = _pool(cfg)
    assert pool["window"]["k"].shape == (1, WIDTH + 1, 2, BLOCK, 256)
    assert pool["full"]["v"].shape == (1, WIDTH + 1, 1, BLOCK, 128)
    pool, got = _prefill_in_chunks(cfg, params, toks, 16, table, pool)
    np.testing.assert_allclose(got, want[CHUNK - 1 : 16 : CHUNK], **TOL)
    assert not np.asarray(pool["full"]["k"][..., 192:]).any()
    for i in range(16, 20):
        pool, logits, _ = mimo_v2.paged_decode(
            params, toks[i][None], jnp.asarray([i]), table[None], pool, cfg, block_size=BLOCK,
            interpret=True,
        )
        np.testing.assert_allclose(logits[0], want[i], **TOL)


def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Sixteen chips hold a sixteenth of the experts each and there is no
    shared expert to count once: their routed parts are the reference's layer
    with every expert held."""
    whole = mimo_v2.MimoV2Config.tiny(n_experts=32, experts_held=32, experts_per_token=4)
    p = mimo_v2.draw_params(jax.random.key(3), whole)["layers"][1]
    assert "s_up" not in p and "e_gate" in p
    p = {**p, "router_bias": 0.1 * jax.random.normal(jax.random.key(4), (32,))}
    m = jax.random.normal(jax.random.key(5), (24, whole.d_model))
    mm = lambda a, w: a @ w.astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want, picks = ref.experts(m, p, ref_config(whole), mm)
    total = 0
    for n in range(16):
        cut = dataclasses.replace(whole, experts_held=2, expert_offset=2 * n)
        share = {**p, **{k: p[k][2 * n : 2 * n + 2] for k in ("e_gate", "e_up", "e_down")}}
        y, counts, idx = latent_moe.moe_ffn(m, share, cut, None)
        np.testing.assert_array_equal(np.sort(idx, -1), np.sort(picks, -1))
        assert int(counts[0]) == int(np.sum((picks >= 2 * n) & (picks < 2 * n + 2)))
        total = total + y
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("wrong, off", [
    ("no_sink", "a window layer's softmax over its keys alone"),
    ("unscaled_values", "values not times 0.707"),
    ("rope_everywhere", "every lane of a head rotated"),
    ("one_theta", "window layers rotated with the full layers' base"),
    ("no_window", "a window layer that sees everything"),
])
def test_the_program_is_not_the_reference_computed_wrongly(tiny, wrong, off):
    """With any one departure the reference is far from the program."""
    cfg, params = tiny
    toks = _tokens(40, seed=8)
    table = jnp.arange(1, WIDTH + 1, dtype=jnp.int32)
    _, got = _prefill_in_chunks(cfg, params, toks, 40, table, _pool(cfg))
    c = ref_config(cfg)
    at = slice(CHUNK - 1, 40, CHUNK)
    np.testing.assert_allclose(got, ref.forward(params, toks, c)[at], **TOL)
    control = ref.forward(params, toks, c, wrong=wrong)[at]
    assert float(jnp.linalg.norm(control - got) / jnp.linalg.norm(got)) > 0.02, off


def _operands(seed, B=3, KH=2, G=2, Dk=24, Dv=16, lanes=32, L=2, N=12, W=8):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (B, KH, G, Dk))
    pk = jax.random.normal(ks[1], (L, N, KH, BLOCK, lanes)).at[..., Dk:].set(0)
    pv = jax.random.normal(ks[2], (L, N, KH, BLOCK, Dv))
    sink = jax.random.normal(ks[3], (KH, G)) + 1.0
    rng = np.random.default_rng(seed)
    tables = jnp.asarray(np.stack([rng.permutation(np.arange(1, N))[:W] for _ in range(B)]), jnp.int32)
    return q, pk, pv, tables, sink


@pytest.mark.parametrize("window", [None, 6], ids=["every position", "a window of 6"])
@pytest.mark.parametrize("lanes", [24, 32], ids=["rows of a key's width", "rows padded"])
def test_the_interpreted_kernel_with_a_sink_and_unequal_widths_is_the_gather(window, lanes):
    """Keys of 24 (in rows of 24 or of 32) beside values of 16, two query
    heads a key/value head (padded to the sublane tile inside), a sink a
    head: the kernel's fold started from ``(sink, 1, 0)`` is the gather's
    softmax with one more column; and each differs from its sinkless self."""
    q, pk, pv, tables, sink = _operands(5, lanes=lanes)
    lengths = jnp.asarray([1, 13, 32])
    want = paged._attend_gathered(q, pk, pv, 1, tables, lengths, sink, window=window)
    got = paged_decode_attention(
        q, pk, pv, jnp.int32(1), tables, lengths, sink, interpret=True, window=window,
    )
    assert got.shape == want.shape == (3, 2, 2, 16)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    sinkless = paged._attend_gathered(q, pk, pv, 1, tables, lengths, window=window)
    assert float(jnp.abs(sinkless - want).max()) > 1e-2
    # by hand, slot 0 (one position): the key's probability is 1 / (1 + exp(sink - s))
    s = jnp.einsum("kgd,kd->kg", q[0], pk[1, tables[0, 0], :, 0, :24]) * 24**-0.5
    np.testing.assert_allclose(
        want[0], jax.nn.sigmoid(s - sink)[..., None] * pv[1, tables[0, 0], :, 0][:, None], rtol=1e-5,
    )


def test_a_sink_of_minus_infinity_is_no_sink():
    q, pk, pv, tables, _ = _operands(6)
    lengths = jnp.asarray([5, 13, 32])
    never = jnp.full((2, 2), -jnp.inf)
    for window in (None, 6):
        plain = paged._attend_gathered(q, pk, pv, 0, tables, lengths, window=window)
        np.testing.assert_allclose(
            paged._attend_gathered(q, pk, pv, 0, tables, lengths, never, window=window), plain, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(
            paged_decode_attention(q, pk, pv, jnp.int32(0), tables, lengths, never, interpret=True, window=window),
            paged_decode_attention(q, pk, pv, jnp.int32(0), tables, lengths, interpret=True, window=window),
            rtol=1e-5, atol=1e-7,
        )
    # prefill's running softmax, from the same start
    qs = jax.random.normal(jax.random.key(7), (8, 2, 2, 24))
    pos = jnp.arange(8, 16)
    args = (qs, pk, pv, 0, tables[0], pos, jnp.asarray(16))
    plain = paged.prefill_attention(*args, block_size=BLOCK, window=6)
    np.testing.assert_allclose(
        paged.prefill_attention(*args, block_size=BLOCK, window=6, sink=never), plain, rtol=1e-5, atol=1e-7)
    sunk = paged.prefill_attention(*args, block_size=BLOCK, window=6, sink=jnp.ones((2, 2)))
    assert float(jnp.abs(sunk - plain).max()) > 1e-2


def test_the_pool_has_a_part_a_kind_each_with_its_kinds_heads_and_the_record_prices_a_position():
    cfg = mimo_v2.MimoV2Config.tiny()
    pool = mimo_v2.init_pool(cfg, 33, BLOCK, slots=3)
    assert pool["full"]["k"].shape == (2, 33, 1, BLOCK, 24) and pool["full"]["v"].shape == (2, 33, 1, BLOCK, 16)
    # ceil((6 + 8) / 4) + 1 = 5 blocks a slot, and the scratch block
    assert pool["window"]["k"].shape == (3, 3 * 5 + 1, 2, BLOCK, 24)
    assert pool["window"]["v"].shape == (3, 3 * 5 + 1, 2, BLOCK, 16)
    # the record states both kinds; (24 + 16) float32 a head: two full layers of one head, three window layers of two
    full, window = mimo_v2.attention_kinds(cfg)
    assert mimo_v2.cache(cfg) == paged.Cache(
        retention=(None, 6), kinds=(full, window), prefill_in_place=True)
    assert (full.layers, window.layers) == (2, 3) and (full.row_bytes, window.row_bytes) == (2 * 160, 3 * 2 * 160)
    assert (full.kv_heads, full.window, full.sink, full.name) == (1, None, False, "full")
    assert (window.kv_heads, window.window, window.sink, window.name) == (2, 6, True, "window")
    assert full.key_width == window.key_width == 24 and full.value_width == 16 and full.key_lanes is None
    published = mimo_v2.MimoV2Config()
    assert published.layer_pattern.count(mimo_v2.FULL) == 9 and len(published.layer_pattern) == 48
    assert [i for i, k in enumerate(published.layer_pattern) if k == mimo_v2.FULL] == [0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert tuple(kind.row_bytes for kind in mimo_v2.cache(published).kinds) == (9 * 2560, 39 * 5120)


def test_in_place_decode_needs_every_kinds_shapes_to_fit(monkeypatch):
    """The family as a whole attends in place only if the full kind's and
    the window kind's shapes both fit the kernel: at the published widths (keys
    in rows of 256) on a TPU, not at the tiny ones, and not if one kind's
    heads overflow the kernel's buffers."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert paged.decode_attends_in_place(mimo_v2.MimoV2Config(), 16)
    assert not paged.decode_attends_in_place(mimo_v2.MimoV2Config.tiny(), 16)
    assert not paged.decode_attends_in_place(mimo_v2.MimoV2Config(swa_n_kv_head=64), 16)
    assert not paged.decode_attends_in_place(mimo_v2.MimoV2Config(), 8)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not paged.decode_attends_in_place(mimo_v2.MimoV2Config(), 16)


def test_a_silent_id_is_never_the_greedy_choice():
    cfg = mimo_v2.MimoV2Config.tiny()
    quiet = dataclasses.replace(cfg, silent_ids=(7, 257))
    params = mimo_v2.draw_params(jax.random.key(3), quiet)
    head = np.asarray(params["lm_head"])
    assert not head[:, [7, 257]].any() and np.abs(head).sum(axis=0).astype(bool).sum() == cfg.vocab_size - 2
    table = jnp.arange(1, WIDTH + 1, dtype=jnp.int32)
    for n in range(3):
        toks = jax.random.randint(jax.random.key(4 + n), (1, 64), 0, cfg.vocab_size)
        logits = np.asarray(mimo_v2.paged_prefill(
            params, toks, jnp.int32(64), jnp.int32(0), table, _pool(cfg), quiet, block_size=BLOCK,
        )[1])
        assert not logits[[7, 257]].any() and logits.argmax() not in (7, 257) and logits.max() > 0
