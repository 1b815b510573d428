"""The prefill attention kernel (``ops/paged_prefill_attention.py``) in the
Pallas interpreter against the fold it stands in for on a TPU
(``paged._prefill_fold``): the same queries, pools and table through
``paged.prefill_attention``'s two arms. Float32 operands, so that what
differs is the order of the running softmax's sums; blocks of 16, chunks of
two tiles of 128 queries."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import paged
from ray_tpu.ops import paged_prefill_attention as ppa

BLOCK, CHUNK, WIDTH, LAYER = 16, 256, 96, 1
TOL = dict(rtol=2e-5, atol=2e-6)  # the interpreted decode kernel's against its gather

# KH, G, Dk, the key pool's lanes, Dv, window, sink, start, length
CASES = {
    "MiMo's full kind: 16 queries a head, keys of 192 in rows of 256 beside values of 128":
        (1, 16, 192, 256, 128, None, False, 512, CHUNK),
    "MiMo's window kind: a window of 128 and a sink, 8 queries a head":
        (2, 8, 192, 256, 128, 128, True, 512, CHUNK),
    "the window kind on a sequence's first chunk": (2, 8, 192, 256, 128, 128, True, 0, CHUNK),
    "Trinity's group of 6, every position": (2, 6, 128, 128, 128, None, False, 1024, CHUNK),
    "32 queries a head: two tiles a head, the second's first stretch in flight behind the first's last":
        (2, 32, 128, 128, 128, None, False, 512, CHUNK),
    "Trinity's group of 6 on a first chunk": (2, 6, 128, 128, 128, None, False, 0, CHUNK),
    "a window wider than the chunk": (1, 6, 128, 128, 128, 2 * CHUNK, False, 3 * CHUNK, CHUNK),
    "a window wider than the chunk, before it fills": (1, 6, 128, 128, 128, 2 * CHUNK, False, CHUNK, CHUNK),
    "a start past several stretches, off their edges": (2, 2, 128, 128, 128, None, False, 1040, CHUNK),
    "a window and a start off the stretches' edges": (2, 2, 128, 128, 128, 100, False, 1040, CHUNK),
    "a last chunk shorter than its bucket": (2, 2, 128, 128, 128, None, False, 768, 150),
    "a last chunk that ends inside its first tile": (2, 2, 128, 128, 128, None, True, 768, 40),
    "a short last chunk under a window with no sink": (2, 2, 128, 128, 128, 100, False, 1024, 40),
}


@functools.cache
def _operands(KH, G, Dk, lanes, Dv, sink, seed=3):
    ks = jax.random.split(jax.random.key(seed), 4)
    pools = (
        jax.random.normal(ks[0], (2, WIDTH + 4, KH, BLOCK, lanes)).at[..., Dk:].set(0),
        jax.random.normal(ks[1], (2, WIDTH + 4, KH, BLOCK, Dv)),
    )
    q = jax.random.normal(ks[2], (CHUNK, KH, G, Dk))
    table = np.random.default_rng(seed).permutation(np.arange(1, WIDTH + 4))[:WIDTH]
    return q, pools, table, 1.0 + jax.random.normal(ks[3], (KH, G)) if sink else None


@pytest.mark.parametrize("case", CASES)
def test_the_prefill_kernel_attends_what_the_fold_attends(case):
    """Every row that holds a token (the padding behind a last chunk means
    nothing in either arm, and is finite in both), with the table's entries
    behind a window on the scratch block, as the engine leaves them: it
    holds NaN here, which the kernel must never copy."""
    KH, G, Dk, lanes, Dv, window, sink, start, length = CASES[case]
    q, (pk, pv), table, sink = _operands(KH, G, Dk, lanes, Dv, sink)
    pos = start + jnp.arange(CHUNK, dtype=jnp.int32)
    attend = functools.partial(
        paged.prefill_attention, pos=pos, n_keys=jnp.int32(start + length), block_size=BLOCK,
        window=window, sink=sink,
    )
    want = attend(q, pk, pv, LAYER, jnp.asarray(table))
    behind = table.copy()
    if window is not None:  # given back: the blocks before the one that holds start - window + 1
        behind[: max(start - window + 1, 0) // BLOCK] = 0
    poisoned = pk.at[:, 0].set(jnp.nan), pv.at[:, 0].set(jnp.nan)
    got = attend(q, *poisoned, LAYER, jnp.asarray(behind), interpret=True)
    assert got.shape == want.shape == (CHUNK, KH, G, Dv) and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got)[:length], np.asarray(want)[:length], **TOL)
    assert np.isfinite(np.asarray(got)).all()
    # The comparison can tell a layer from its neighbour, a table from another and a start from the next block's.
    for wrong in (
        attend(q, pk, pv, 0, jnp.asarray(table)),
        attend(q, pk, pv, LAYER, jnp.asarray(table[::-1].copy())),
        attend(q, pk, pv, LAYER, jnp.asarray(table), pos=pos + BLOCK),
    ):
        assert np.abs(np.asarray(wrong) - np.asarray(got))[:length].max() > 0.02


def test_a_sink_of_minus_infinity_is_no_sink_and_a_sink_takes_its_share():
    KH, G = 2, 8
    q, (pk, pv), table, sink = _operands(KH, G, 192, 256, 128, True)
    attend = functools.partial(
        ppa.paged_prefill_attention, q, pk, pv, jnp.int32(LAYER), jnp.asarray(table), jnp.int32(512),
        jnp.int32(512 + CHUNK), window=128, interpret=True,
    )
    plain = attend()
    np.testing.assert_allclose(attend(jnp.full((KH, G), -1e30)), plain, rtol=1e-5, atol=1e-7)
    sunk = attend(sink)
    assert float(jnp.abs(sunk - plain).max()) > 1e-2
    # a head's sink is its own: exchanged between the heads, the heads' rows move
    moved = np.abs(np.asarray(attend(sink[::-1, ::-1]) - sunk)).max(axis=(0, 3))
    assert np.median(moved) > 1e-3


def test_the_tile_and_the_stretch_follow_from_the_shapes_and_the_kernel_fits_whole_tiles_only():
    """A tile takes the positions that keep its rows (the query heads of a
    KV head beside them) within 4,096, a stretch the keys that keep its
    scores within 2M elements; a window bounds both; the kernel takes chunks
    of whole tiles at widths of whole lane tiles."""
    # MiMo's full kind, Solar and MiMo's window heads, Trinity: 4,096, 4,096 and 3,072 rows a tile
    assert ppa.tile(2048, 16) == 256 and ppa.tile(2048, 8) == 512 and ppa.tile(2048, 6) == 512
    assert all(ppa.stretch(ppa.tile(2048, g), g, 16) == 512 for g in (16, 8, 6))
    # a tile divides the chunk: the smaller buckets of a mixed queue
    assert ppa.tile(1024, 6) == 512 and ppa.tile(256, 6) == 256 and ppa.tile(384, 6) == ppa.tile(128, 6) == 128
    # a window of 128 is read under tiles of 128, its whole walk one stretch; one of 4,096 like no window
    assert ppa.tile(2048, 8, window=128) == 128 and ppa.stretch(128, 8, 16, window=128) == 256
    assert ppa.tile(2048, 6, window=4096) == 512 and ppa.stretch(512, 6, 16, window=4096) == 512
    assert ppa.tile(2048, 8, window=100) == 128 and ppa.stretch(128, 8, 16, window=100) == 256
    assert ppa.tile(2048, 8, window=200) == 256 and ppa.stretch(256, 8, 16, window=200) == 512
    # more query heads than the rows hold at 128 positions: the stretch gives way
    assert ppa.tile(2048, 64) == 128 and ppa.stretch(128, 64, 16) == 256
    assert ppa.fits(2048, 16, 256, 128, 16) and ppa.fits(4096, 8, 128, 128, 16)
    assert not ppa.fits(2048, 16, 192, 128, 16)  # a key's 192 lanes: the pool lays them in 256
    assert not ppa.fits(2048, 16, 256, 64, 16) and not ppa.fits(2048, 16, 256, 128, 8)
    assert not ppa.fits(2100, 16, 256, 128, 16)  # no whole tile
    # a chunk's length at the least: a short prompt's whole prefill keeps the fold
    assert not ppa.fits(1024, 16, 256, 128, 16) and not ppa.fits(128, 8, 128, 128, 16)
    # a window no longer than a tile of the kind's queries: MiMo's 128, and not Trinity's 4,096
    assert ppa.fits(2048, 8, 256, 128, 16, window=128) and ppa.fits(2048, 6, 128, 128, 16, window=512)
    assert not ppa.fits(2048, 6, 128, 128, 16, window=4096) and not ppa.fits(2304, 6, 128, 128, 16, window=512)


def test_off_a_tpu_and_at_shapes_that_do_not_tile_the_choice_is_the_fold(monkeypatch):
    """``prefill_attention`` lowered here is the fold, whatever the shapes;
    a program lowered for a TPU holds the kernel where the shapes fit
    (``tests/test_tpu_aot.py`` compiles those). ``prefill_attends_in_kernel``
    answers for a family by its record and its kinds' shapes."""
    from ray_tpu.models import afmoe, llama, mimo_v2, solar_open2

    _, (pk, pv), table, _ = _operands(2, 2, 128, 128, 128, False)
    attend = functools.partial(paged.prefill_attention, block_size=BLOCK)
    shapes = lambda n: (  # noqa: E731
        jax.ShapeDtypeStruct((n, 2, 2, 128), jnp.float32), pk, pv, 0, jnp.asarray(table),
        jax.ShapeDtypeStruct((n,), jnp.int32), jnp.int32(n),
    )
    assert "paged_prefill_attention" not in jax.jit(attend).lower(*shapes(2048)).as_text()
    jaxpr = str(jax.make_jaxpr(attend)(*shapes(2048)))
    assert "platform_index" in jaxpr and "paged_prefill_attention" in jaxpr  # both arms, chosen at lowering
    for short in (100, 1024):  # no whole tile; a short prompt's whole prefill
        small = str(jax.make_jaxpr(attend)(*shapes(short)))
        assert "paged_prefill_attention" not in small and "platform_index" not in small

    served = [mimo_v2.MimoV2Config(), afmoe.AfmoeConfig(), solar_open2.SolarOpen2Config()]
    assert not any(paged.prefill_attends_in_kernel(cfg, 16, 2048) for cfg in served)  # no TPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert all(paged.prefill_attends_in_kernel(cfg, 16, 2048) for cfg in served)  # Trinity: its full layers
    only_window = dataclasses.replace(served[1], layer_types=(afmoe.SLIDING,) * 4, n_dense=1)
    assert paged.prefill_attends_in_kernel(only_window, 16, 2048)  # asked by kind of layer, not by layer
    assert all(paged.prefill_attends_in_kernel(cfg, 16, 4096) for cfg in served)
    assert not any(paged.prefill_attends_in_kernel(cfg, 16, tokens) for cfg in served for tokens in (77, 128, 1024))
    assert not any(paged.prefill_attends_in_kernel(cfg, 8, 2048) for cfg in served)
    assert not paged.prefill_attends_in_kernel(mimo_v2.MimoV2Config.tiny(), 16, 2048)

    class FourChips:
        size = 4

    assert not paged.prefill_attends_in_kernel(served[0], 16, 2048, mesh=FourChips())
    # a family whose prefill gathers its table never calls prefill_attention
    assert not paged.prefill_attends_in_kernel(llama.LlamaConfig(), 16, 2048)


def _engine(family):
    from ray_tpu.llm import LLMConfig, LLMEngine
    from ray_tpu.models import afmoe, llama, mimo_v2, solar_open2

    tiny = {
        "mimo_v2": mimo_v2.MimoV2Config.tiny, "afmoe": afmoe.AfmoeConfig.tiny,
        "solar_open2": solar_open2.SolarOpen2Config.tiny, "llama": llama.LlamaConfig.tiny,
    }[family]
    chunks = {} if family == "llama" else {"prefill_chunk_tokens": 16}
    return LLMEngine(LLMConfig(
        model_config=tiny(max_seq=128), max_slots=2, max_seq=128, prefill_buckets=(16, 64),
        kv_block_size=16, num_kv_blocks=2 * 8 + 1, seed=0, enable_prefix_caching=False, **chunks,
    ))


@pytest.mark.parametrize("family", ["mimo_v2", "afmoe", "solar_open2", "llama"])
def test_the_engine_counts_its_prefill_programs_by_the_arm_of_their_attention(family, monkeypatch):
    """``prefill_attn_kernel_chunks`` / ``prefill_attn_fold_chunks``: every
    prefill program launched, by what ``paged.prefill_attends_in_kernel``
    says of its bucket, for the families whose prefill reads the pool where
    it lies; a family that gathers its table has neither counter."""
    from ray_tpu.llm import SamplingParams

    engine = _engine(family)
    tokens = np.random.default_rng(0).integers(3, 200, size=40).tolist()
    engine.generate([tokens], SamplingParams(max_tokens=2))
    if family == "llama":
        assert not [k for k in engine.stats if k.startswith("prefill_attn_")]
        return
    assert engine.stats["prefill_attn_kernel_chunks"] == 0  # lowered here for a CPU
    assert engine.stats["prefill_attn_fold_chunks"] == engine.stats["prefill_chunks"] == 3
    # what the engine asks is paged's one function, of each launch's bucket
    asked = []
    monkeypatch.setattr(
        paged, "prefill_attends_in_kernel",
        lambda cfg, bs, tokens, mesh=None: asked.append((bs, tokens)) or tokens == 16,
    )
    engine.generate([tokens[:20]], SamplingParams(max_tokens=2))
    assert asked == [(16, 16), (16, 16)]
    assert engine.stats["prefill_attn_kernel_chunks"] == 2 and engine.stats["prefill_attn_fold_chunks"] == 3
