"""``ops.moe_gmm``: the experts' grouped product as a Pallas kernel, run in
the interpreter here, against ``jax.lax.ragged_dot`` on the same operands;
the walk it is handed (which experts it copies, and how often); and the arm
``moe_ffn`` and the engine take, by platform, mesh and widths."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.core.config import Config
from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams
from ray_tpu.models import latent_moe
from ray_tpu.models.kimi_linear import KimiLinearConfig
from ray_tpu.ops import moe_gmm

pytestmark = pytest.mark.timeout(600)

ROWS_A_PASS = latent_moe.ROWS_A_PASS


def operands(m, E, K, N, dtype=jnp.bfloat16, seed=0):
    """Rows of unit variance and weights that keep it: results of order 1."""
    kx, kw = jax.random.split(jax.random.key(seed))
    x = jax.random.normal(kx, (m, K), jnp.float32).astype(dtype)
    w = (jax.random.normal(kw, (E, K, N), jnp.float32) * K**-0.5).astype(dtype)
    return x, w


def assert_is_ragged_dot(x, w, sizes):
    """The kernel's rows against ``ragged_dot``'s in float32: as near as the
    operands' dtype rounds a float32 sum taken in another order, and exactly
    zero behind the last group."""
    sizes = jnp.asarray(sizes, jnp.int32)
    got = moe_gmm.gmm(x, w, sizes, interpret=True)
    assert got.dtype == x.dtype and got.shape == (x.shape[0], w.shape[2])
    want = jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32)
    landed = int(sizes.sum())
    ulp = float(jnp.finfo(x.dtype).eps)  # bf16: 2^-7, so half an ulp is 2^-8 of the value
    np.testing.assert_allclose(
        np.asarray(got[:landed], np.float32), np.asarray(want[:landed]), rtol=ulp / 2, atol=1e-4
    )
    assert not np.asarray(got[landed:], np.float32).any()


# The three families' expert matrices, both directions, at the rows of their
# cells' decode steps (64 x 22, 16 x 8, 32 x 8 picks), a few experts of each.
@pytest.mark.parametrize(
    "m,K,N,sizes",
    [
        (1408, 1024, 2688, [3, 0, 140, 2, 1]),  # nemotron_h: up; 146 of 1,408 rows land
        (1408, 2688, 1024, [0, 5, 1, 250, 0]),  # nemotron_h: down
        (128, 2304, 1024, [1, 0, 17, 2, 12]),  # kimi_linear: gate and up
        (128, 1024, 2304, [128, 0, 0]),  # kimi_linear: down, one expert takes every row
        (256, 7168, 2048, [2, 1, 0, 13]),  # mla_moe: gate and up, in column tiles of 512
        (256, 2048, 7168, [0, 0, 9, 7]),  # mla_moe: down, in column tiles of 1,792
    ],
    ids=["nemotron_up", "nemotron_down", "kimi_up", "kimi_down", "axk1_up", "axk1_down"],
)
def test_the_kernel_is_ragged_dot_at_the_families_widths(m, K, N, sizes):
    x, w = operands(m, len(sizes), K, N)
    assert_is_ragged_dot(x, w, sizes)


LAYOUTS = {
    "empty_groups_at_the_front": [0, 0, 5, 130, 7, 20],
    "empty_groups_in_the_middle": [5, 0, 0, 130, 0, 20],
    "empty_groups_at_the_end": [5, 130, 20, 0, 0, 0],
    "one_group_holds_every_row": [0, 0, 384, 0, 0, 0],
    "every_group_one_row": [1, 1, 1, 1, 1, 1],
    "rows_behind_the_last_group": [3, 4, 5, 6, 7, 8],
    "a_group_straddles_a_row_tile": [100, 60, 200, 0, 10, 14],
    "every_row_lands": [64, 64, 64, 64, 64, 64],
    "no_row_lands": [0, 0, 0, 0, 0, 0],
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_kernel_is_ragged_dot_whatever_the_groups(monkeypatch, layout, dtype):
    """Three row tiles of 128 and, the tile budget cut to one lane tile of
    256 rows, three column tiles: every layout of groups over them."""
    monkeypatch.setattr(moe_gmm, "_TILE_BYTES", 256 * 128 * jnp.dtype(dtype).itemsize)
    kernel = moe_gmm.gmm.__wrapped__  # traced under the budget above
    monkeypatch.setattr(moe_gmm, "gmm", lambda *a, **kw: kernel(*a, **kw))
    assert moe_gmm._tile_n(256, 384, jnp.dtype(dtype).itemsize) == 128
    x, w = operands(384, 6, 256, 384, dtype)
    assert_is_ragged_dot(x, w, LAYOUTS[layout])


@pytest.mark.parametrize("m", [24, 40, 200, ROWS_A_PASS], ids=lambda m: f"m{m}")
def test_rows_that_are_no_whole_tiles_and_a_whole_pass(m):
    """A few rows (padded up to a tile of 32 or 48, or to a second tile of
    128) and a pass of ``ROWS_A_PASS`` rows that reaches the experts in the
    middle only, as a long prompt's later passes do."""
    E = 8
    sizes = np.zeros(E, np.int32)
    sizes[2:6] = [m // 5, m // 3, 0, m // 4]
    x, w = operands(m, E, 256, 128)
    assert_is_ragged_dot(x, w, sizes)


def walk(sizes, m, tm=128):
    groups, tiles, offsets, count = moe_gmm._visits(jnp.asarray(sizes, jnp.int32), m // tm, tm)
    n = int(count)
    assert n <= len(sizes) + m // tm - 1 == groups.shape[0]
    return np.asarray(groups)[:n], np.asarray(tiles)[:n], np.asarray(offsets)


@pytest.mark.parametrize(
    "sizes,m",
    [
        ([0, 0, 5, 130, 7, 20], 384), ([100, 60, 200, 0, 10, 14], 384), ([0] * 6, 384),
        ([3, 0, 2, 0, 0, 0, 4, 1] * 16, 1408),  # a decode step of 64 x 22 picks: 160 land
        ([0] * 40 + [45] * 45 + [23] + [0] * 42, ROWS_A_PASS),  # a pass reaching 46 of 128
    ],
    ids=["front", "straddle", "none", "decode_step", "pass"],
)
def test_the_walk_copies_each_touched_expert_once_and_no_other(sizes, m):
    """The weight tile a visit needs is copied when its group differs from
    the visit's before it (the pipeline keeps a block whose index stays). So
    the copies a column tile are the runs of equal group numbers in the walk:
    one a group that holds a row, a straddling group's visits being
    consecutive, none for an empty group, and none for the tiles behind the
    last row, which are visited under the last group's number."""
    groups, tiles, offsets = walk(sizes, m)
    touched = [g for g, s in enumerate(sizes) if s]
    runs = [int(g) for i, g in enumerate(groups) if i == 0 or g != groups[i - 1]]
    assert runs == (touched or [0])  # nothing landed: the walk names expert 0 and multiplies nothing
    assert sorted(set(tiles.tolist())) == list(range(m // 128))  # every row tile is written
    assert (np.diff(tiles) >= 0).all()  # a tile's visits are consecutive: its block stays put
    for g, t in zip(groups, tiles):  # a visit before the last row owns a row of its tile
        lo, hi = offsets[g], offsets[g + 1]
        assert (hi > t * 128 and lo < (t + 1) * 128) or t * 128 >= sum(sizes)
    assert offsets.tolist() == np.concatenate([[0], np.cumsum(sizes)]).tolist()


def test_tile_widths_come_from_the_shapes():
    """Whole matrices (one contiguous copy an expert) where they fit the
    budget, else the widest divisor in whole lane tiles that does."""
    widths = {(K, N): moe_gmm._tile_n(K, N, 2) for K, N in [
        (1024, 2688), (2688, 1024), (2304, 1024), (1024, 2304), (7168, 2048), (2048, 7168)]}
    assert widths == {
        (1024, 2688): 2688, (2688, 1024): 1024, (2304, 1024): 1024, (1024, 2304): 2304,
        (7168, 2048): 512, (2048, 7168): 1792,
    }
    assert all(N % tn == 0 and K * tn * 2 <= moe_gmm._TILE_BYTES for (K, N), tn in widths.items())
    assert moe_gmm._tile_n(64, 96, 4) == 96  # no whole lane tiles: all of it (the interpreter)
    assert moe_gmm._tile_n(40000, 256, 2) == 0  # not even one lane tile fits


# -- the arm ------------------------------------------------------------------------


def test_the_arm_follows_platform_mesh_and_widths_and_nothing_a_user_sets(monkeypatch):
    import inspect

    two_chips = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",))
    one_chip = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tp",))
    cases = [
        (1024, 2688, None, True), (2688, 1024, one_chip, True), (7168, 2048, None, True),
        (2304, 1024, two_chips, False),  # a Mosaic call is not partitioned
        (1024, 2700, None, False), (1000, 2688, None, False),  # no whole lane tiles
        (64, 32, None, False),  # every tiny configuration of the tests
        (40000, 256, None, False),  # one lane tile of the weights past the budget
    ]
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        for K, N, mesh, on_tpu in cases:
            assert moe_gmm.fits(K, N, jnp.bfloat16, mesh) == (on_tpu and backend == "tpu")
        layer = {"e_up": jnp.zeros((2, 256, 128)), "e_down": jnp.zeros((2, 128, 256))}
        assert latent_moe.experts_in_kernel(layer, jnp.bfloat16) == (backend == "tpu")
        assert not latent_moe.experts_in_kernel(layer, jnp.bfloat16, two_chips)
        odd = {"e_up": jnp.zeros((2, 256, 96)), "e_down": jnp.zeros((2, 96, 256))}
        assert not latent_moe.experts_in_kernel(odd, jnp.bfloat16)
    assert list(inspect.signature(moe_gmm.fits).parameters) == ["k", "n", "dtype", "mesh"]
    named = [f.name for c in (LLMConfig, Config, KimiLinearConfig) for f in dataclasses.fields(c)]
    assert not [n for n in named if "gmm" in n.lower() or "ragged" in n.lower() or "tile" in n.lower()]


def kimi_engine():
    cfg = KimiLinearConfig.tiny(d_model=128, moe_d_ff=128)  # the experts' widths: whole lane tiles
    return LLMEngine(LLMConfig(
        model_config=cfg, max_slots=2, max_seq=64, kv_block_size=16, enable_prefix_caching=False))


def generate(engine):
    engine.add_request("a", [5, 9, 2, 7, 7, 1], SamplingParams(max_tokens=4, temperature=0.0))
    engine.add_request("b", [3] * 20, SamplingParams(max_tokens=3, temperature=0.0))
    while engine.has_unfinished():
        engine.step()
    return {r.request_id: r.generated for r in engine.pop_finished()}


def test_the_engine_counts_its_programs_by_the_arm_they_were_built_with(monkeypatch):
    """On the CPU every program of a model with experts runs ``ragged_dot``.
    With the backend patched to ``"tpu"`` (and the kernel to its interpreter,
    which is what a CPU can run of it) the same engine is built with the
    kernel, from ``init_params``' balancing pass on, and generates the same
    tokens."""
    on_cpu = kimi_engine()
    assert on_cpu._moe_arm == "moe_gmm_ragged_steps"
    tokens = generate(on_cpu)
    programs = 2 + on_cpu.stats["decode_attn_gather_steps"]  # two prefills and the decode steps
    assert on_cpu.stats["moe_gmm_ragged_steps"] == programs > 2
    assert on_cpu.stats["moe_gmm_kernel_steps"] == 0

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(moe_gmm, "gmm", functools.partial(moe_gmm.gmm, interpret=True))
    on_tpu = kimi_engine()
    assert on_tpu._moe_arm == "moe_gmm_kernel_steps"
    assert generate(on_tpu) == tokens
    assert on_tpu.stats["moe_gmm_kernel_steps"] == programs
    assert on_tpu.stats["moe_gmm_ragged_steps"] == 0


def test_a_model_without_experts_has_neither_counter():
    from ray_tpu.models import llama

    engine = LLMEngine(LLMConfig(
        model_config=llama.LlamaConfig.tiny(n_layer=1), max_slots=2, max_seq=64, kv_block_size=16))
    assert engine._moe_arm is None
    assert not [k for k in engine.stats if "moe_gmm" in k]
