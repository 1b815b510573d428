"""``ops.state_step``: a slot's recurrent state stepped where it lies, in the
Pallas interpreter on the CPU. Each tile body against the plain step at the
three served shapes; rows that are kept and the scratch row bit for bit; the
choice between the kernel and the plain step; the seam that hands a family's
step the one or the other; and what the three families' engines count here.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.models import kda, kimi_linear, nemotron_h, paged, solar_open2  # noqa: E402
from ray_tpu.ops import state_step  # noqa: E402
from ray_tpu.ops.delta_rule import kda_step  # noqa: E402
from ray_tpu.ops.ssd import ssd_step  # noqa: E402

pytestmark = pytest.mark.timeout(600)

LAYERS, LAYER = 3, 1  # the layer stepped lies between two that are not


def kda_operands(key, rows, H, dk, dv):
    """As ``models/kda.py:_kda_inputs`` hands them over: unit keys, queries
    scaled, log decays below zero, ``beta`` in (0, 2)."""
    ks = jax.random.split(key, 5)
    l2 = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    return (
        l2(jax.random.normal(ks[0], (rows, H, dk))) * dk**-0.5,
        l2(jax.random.normal(ks[1], (rows, H, dk))),
        jax.random.normal(ks[2], (rows, H, dv)),
        -jax.random.uniform(ks[3], (rows, H, dk), minval=0.001, maxval=1.6),
        2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (rows, H))),
    )


def ssd_operands(key, rows, H, P, N, groups):
    ks = jax.random.split(key, 6)
    return (
        jax.random.normal(ks[0], (rows, H, P)),
        jax.nn.softplus(jax.random.normal(ks[1], (rows, H)) - 1.0),
        -jnp.exp(jax.random.normal(ks[2], (H,))),
        jax.random.normal(ks[3], (rows, groups, N)),
        jax.random.normal(ks[4], (rows, groups, N)),
        jax.random.normal(ks[5], (H,)),
    )


def stepped(step, operands, state, rows, keep):
    """``step`` on rows ``[:rows]`` of layer ``LAYER`` through the interpreted
    kernel: ``(out, state)``."""

    @jax.jit
    def run(state):
        out, held = step(*operands, state_step.Rows(state, LAYER, rows, keep, interpret=True))
        return out, held.state

    return run(state)


def assert_only_live_rows_moved(new, old, rows, keep):
    """Every layer but ``LAYER``, the rows past ``rows`` (the scratch row) and
    the rows that are kept: as they were, bit for bit; the live rows moved."""
    new, old = np.asarray(new), np.asarray(old)
    untouched = np.ones(old.shape[:2], bool)
    untouched[LAYER, :rows] = np.asarray(keep)
    np.testing.assert_array_equal(new[untouched], old[untouched])
    assert not np.array_equal(new[~untouched], old[~untouched])


# The three cells' states: heads, tile, groups of B and C (None: KDA); rows: an
# odd batch, fewer than the slots.
SERVED = {
    "kimi-linear": (32, (128, 128), None, 3),
    "solar-open2": (64, (128, 128), None, 3),
    "nemotron-3-super": (128, (64, 128), 8, 5),
}


@pytest.mark.parametrize("cell", sorted(SERVED))
def test_a_tile_body_is_the_plain_step_at_a_served_shape(cell):
    """``state_step.kda`` / ``ssd`` through the kernel against ``kda_step`` /
    ``ssd_step`` on the same rows, at the served heads and tiles with an odd
    batch: output and state to float32 rounding; a kept row's state, the
    scratch row and the other layers bit for bit; a kept row's output, which
    means nothing, is zero from the kernel."""
    H, (a, b), groups, rows = SERVED[cell]
    assert state_step.tiles(H, a, b)
    key = jax.random.key(H)
    state = jax.random.normal(key, (LAYERS, rows + 2, H, a, b), jnp.float32)
    keep = jnp.arange(rows) == 1
    if groups is None:
        operands, step, plain = kda_operands(key, rows, H, a, b), state_step.kda, kda_step
    else:
        operands, step, plain = ssd_operands(key, rows, H, a, b, groups), state_step.ssd, ssd_step
    out, new = stepped(step, operands, state, rows, keep)
    want_out, want = plain(*operands, state[LAYER, :rows])
    live = ~np.asarray(keep)
    scale = float(jnp.max(jnp.abs(want_out)))
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(want_out)[live], rtol=2e-5, atol=2e-6 * scale)
    np.testing.assert_allclose(np.asarray(new[LAYER, :rows])[live], np.asarray(want)[live], rtol=2e-5, atol=2e-6)
    if groups is None:  # (SSD's is ``D x``: the term that never saw the state)
        np.testing.assert_array_equal(np.asarray(out)[~live], 0.0)
    assert_only_live_rows_moved(new, state, rows, keep)
    # given the rows' values and not the rows, either is the plain step itself
    again_out, again = step(*operands, state[LAYER, :rows])
    np.testing.assert_array_equal(again_out, want_out)
    np.testing.assert_array_equal(again, want)


@pytest.mark.parametrize("keep", ["none_given", "every_row"])
def test_rows_all_live_or_all_kept(keep):
    """No ``keep`` steps every row; every row kept moves nothing at all."""
    rows, H, d = 4, 8, 128
    key = jax.random.key(7)
    state = jax.random.normal(key, (LAYERS, rows + 1, H, d, d), jnp.float32)
    operands = kda_operands(key, rows, H, d, d)
    if keep == "none_given":
        out, new = stepped(state_step.kda, operands, state, rows, None)
        want_out, want = kda_step(*operands, state[LAYER, :rows])
        np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(new[LAYER, :rows], want, rtol=2e-5, atol=2e-6)
        assert_only_live_rows_moved(new, state, rows, np.zeros(rows, bool))
    else:
        out, new = stepped(state_step.kda, operands, state, rows, jnp.ones(rows, bool))
        np.testing.assert_array_equal(new, state)
        np.testing.assert_array_equal(out, 0.0)


@pytest.mark.parametrize(
    "kept", ["0", "12", "01", "0134", "3"], ids=lambda k: "rows_" + k + "_kept",
)
def test_kept_rows_move_nothing_wherever_they_lie(kept):
    """A kept row rides on the block of the last live row before it and puts
    its own through where there is none: first, last, in a run, between live
    rows, over two head groups, its state is as it was and the live rows'
    are the plain step's."""
    rows, H, d = 5, 64, 128
    assert state_step.head_group(H, d, d) == H // 2
    key = jax.random.key(11)
    state = jax.random.normal(key, (LAYERS, rows + 1, H, d, d), jnp.float32)
    operands = kda_operands(key, rows, H, d, d)
    keep = jnp.asarray([str(r) in kept for r in range(rows)])
    out, new = stepped(state_step.kda, operands, state, rows, keep)
    want_out, want = kda_step(*operands, state[LAYER, :rows])
    live = ~np.asarray(keep)
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(want_out)[live], rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(new[LAYER, :rows])[live], np.asarray(want)[live], rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(out)[~live], 0.0)
    assert_only_live_rows_moved(new, state, rows, keep)


@pytest.mark.parametrize(
    "H,a,b,groups", [(2, 16, 16, None), (4, 8, 16, 2), (16, 64, 128, 4), (24, 32, 256, None)],
    ids=["kimi-tiny", "nemotron-tiny", "two-blocks-of-a-group", "wide-values"],
)
def test_the_interpreter_takes_shapes_the_chip_would_not(H, a, b, groups):
    """Tiles off the ``(8, 128)`` tiling (the tiny configurations'), a head
    group that is a part of ``B`` and ``C``'s group, values wider than keys:
    the same numbers as the plain step."""
    rows = 3
    key = jax.random.key(a)
    state = jax.random.normal(key, (LAYERS, rows + 1, H, a, b), jnp.float32)
    keep = jnp.arange(rows) == 2
    if groups is None:
        operands, step, plain = kda_operands(key, rows, H, a, b), state_step.kda, kda_step
    else:
        operands, step, plain = ssd_operands(key, rows, H, a, b, groups), state_step.ssd, ssd_step
    out, new = stepped(step, operands, state, rows, keep)
    want_out, want = plain(*operands, state[LAYER, :rows])
    np.testing.assert_allclose(out[:2], want_out[:2], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(new[LAYER, :2], want[:2], rtol=2e-5, atol=2e-6)
    assert_only_live_rows_moved(new, state, rows, keep)


def test_a_head_group_is_the_most_heads_that_fit_a_block():
    """32 heads of 128 x 128 are one block of 2 MiB, 64 two; 128 heads of 64
    x 128 two as well; heads that are no whole sublane tiles stay together."""
    assert state_step.head_group(32, 128, 128) == 32
    assert state_step.head_group(64, 128, 128) == 32
    assert state_step.head_group(128, 64, 128) == 64
    assert state_step.head_group(2, 16, 16) == 2
    assert state_step.head_group(12, 128, 128) == 12


@pytest.mark.parametrize(
    "why,H,a,b",
    [("a tile of 100 columns", 32, 128, 100), ("a tile of 60 rows", 32, 60, 128),
     ("a tile taller than one transposition", 32, 256, 128), ("heads in no whole sublane tiles", 12, 128, 128),
     ("the tiny configurations", 2, 16, 16)],
)
def test_tiles_refuses_a_shape_off_the_tiling(why, H, a, b):
    assert not state_step.tiles(H, a, b), why
    assert not state_step.fits(H, a, b)


@pytest.mark.parametrize("cell", sorted(SERVED))
def test_fits_is_decided_by_platform_mesh_and_shapes(cell, monkeypatch):
    """The served shapes tile; here, on the CPU, nothing fits; on a TPU they
    do, but not under a mesh of two chips (the compiler cannot partition a
    Mosaic call). No argument and no environment variable says which."""
    H, (a, b), _, _ = SERVED[cell]
    assert state_step.tiles(H, a, b)
    assert jax.default_backend() == "cpu" and not state_step.fits(H, a, b)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert state_step.fits(H, a, b)
    assert state_step.fits(H, a, b, Mesh(np.array(jax.devices()[:1]), ("tp",)))
    assert not state_step.fits(H, a, b, Mesh(np.array(jax.devices()[:2]), ("tp",)))
    state = jax.ShapeDtypeStruct((2, 5, H, a, b), jnp.float32)
    assert paged.state_steps_in_kernel(state)
    assert not paged.state_steps_in_kernel(jax.ShapeDtypeStruct((2, 5, H, a, 100), jnp.float32))


def _mixer(family):
    """A family's decode mixer at the kernel's shapes (8 heads, a state of
    whole tiles) over a tiny model: ``(u -> step(state0, tail0), its
    configuration, the state's tile [H, a, b])``."""
    if family == "nemotron_h":
        cfg = nemotron_h.NemotronHConfig.tiny(mamba_heads=8, mamba_head_dim=8, ssm_groups=2, ssm_state=128)
        params = nemotron_h.draw_params(jax.random.key(0), cfg)
        p = next(p for kind, p, _ in nemotron_h._layers(params, cfg) if kind == "M")
        return (lambda u: lambda h, tail: nemotron_h.mamba_decode(u, p, cfg, h, tail)), cfg, (8, 8, 128)
    if family == "kimi_linear":
        cfg = kimi_linear.KimiLinearConfig.tiny(kda_heads=8, kda_head_dim=128)
        params = kimi_linear.draw_params(jax.random.key(0), cfg)
        p = next(p for _, p, kind, _ in kimi_linear._layers(params, cfg) if kind == "kda")
    else:
        cfg = solar_open2.SolarOpen2Config.tiny(kda_heads=8, kda_head_dim=128)
        params = solar_open2.draw_params(jax.random.key(0), cfg)
        p = next(p for _, kind, p, _ in solar_open2._layers(params, cfg) if kind == solar_open2.KDA)
    return (lambda u: lambda S, tail: kda.kda_decode(u, p, cfg, S, tail)), cfg, (8, 128, 128)


@pytest.mark.parametrize("family", ["kimi_linear", "solar_open2", "nemotron_h"])
def test_the_seam_hands_a_familys_step_the_rows_or_their_values(family):
    """``paged.state_decode`` around each family's decode mixer, the state's
    tiles the kernel's: through the interpreted kernel and, as a program
    lowered for the CPU chooses, through the plain step. The same output for
    the live rows, state and tail to float32 rounding, a kept row's state and
    tail and the scratch row bit for bit either way."""
    step_of, cfg, tile = _mixer(family)
    rows, slots = 3, 4
    key = jax.random.key(3)
    state = 0.1 * jax.random.normal(key, (LAYERS, slots + 1, *tile), jnp.float32)
    conv = jax.random.normal(key, (LAYERS, slots + 1, cfg.conv_kernel - 1, cfg.conv_dim)).astype(cfg.dtype)
    u = jax.random.normal(key, (rows, cfg.d_model)).astype(cfg.dtype)
    keep = jnp.asarray([False, True, False])
    assert state_step.tiles(*tile)
    run = lambda interpret: jax.jit(  # noqa: E731
        lambda state, conv: paged.state_decode(step_of(u), state, conv, LAYER, rows, keep, interpret=interpret)
    )(state, conv)
    (out_k, state_k, conv_k), (out_p, state_p, conv_p) = run(True), run(False)
    live = ~np.asarray(keep)
    np.testing.assert_allclose(np.asarray(out_k)[live], np.asarray(out_p)[live], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(state_k, state_p, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(conv_k, conv_p)
    for new in (state_k, state_p):
        assert_only_live_rows_moved(new, state, rows, keep)
    for new in (conv_k, conv_p):
        assert_only_live_rows_moved(new.astype(jnp.float32), conv.astype(jnp.float32), rows, keep)


def test_the_seam_chooses_by_platform_where_the_tiles_fit_and_not_elsewhere():
    """At tiles the kernel takes, the program holds both arms under
    ``platform_dependent`` (lowered for a TPU it is the kernel, here the plain
    step: ``tests/test_tpu_aot.py`` compiles the one, this file runs the
    other); at any other, the plain step alone."""
    def traced(d):
        operands = kda_operands(jax.random.key(0), 2, 8, d, d)
        step = lambda S, tail: (*state_step.kda(*operands, S), tail)  # noqa: E731
        state = jax.ShapeDtypeStruct((1, 3, 8, d, d), jnp.float32)
        conv = jax.ShapeDtypeStruct((1, 3, 3, 16), jnp.float32)
        return str(jax.make_jaxpr(lambda s, c: paged.state_decode(step, s, c, 0, 2))(state, conv))

    fitting, tiny = traced(128), traced(16)
    assert "platform_index" in fitting and "state_step_kda" in fitting
    assert "platform_index" not in tiny and "pallas_call" not in tiny


ENGINES = {
    "kimi_linear": kimi_linear.KimiLinearConfig,
    "solar_open2": solar_open2.SolarOpen2Config,
    "nemotron_h": nemotron_h.NemotronHConfig,
}


@pytest.mark.parametrize("family", sorted(ENGINES))
def test_an_engine_on_the_cpu_counts_plain_state_steps_only(family):
    """Each family's engine, built here: the arm is chosen once, at
    construction, from the platform and the pool's state; every decode
    program launched counts a plain step and none a kernel's."""
    engine = LLMEngine(LLMConfig(
        model_config=ENGINES[family].tiny(max_seq=128), max_slots=3, max_seq=128,
        prefill_buckets=(32, 64), kv_block_size=16, prefix_chunk=16, seed=0,
        enable_prefix_caching=False,
    ))
    assert engine._state_arm == "state_plain_steps"
    assert engine.stats["state_kernel_steps"] == engine.stats["state_plain_steps"] == 0
    rng = np.random.default_rng(0)
    engine.generate(
        [rng.integers(3, 500, size=n).tolist() for n in (9, 20)], SamplingParams(max_tokens=5)
    )
    decodes = engine.stats["decode_attn_kernel_steps"] + engine.stats["decode_attn_gather_steps"]
    assert decodes >= 4
    assert engine.stats["state_plain_steps"] == decodes and engine.stats["state_kernel_steps"] == 0


def test_an_engine_without_a_state_a_slot_counts_neither():
    from ray_tpu.models.llama import LlamaConfig

    engine = LLMEngine(LLMConfig(
        model_config=LlamaConfig.tiny(n_layer=2, d_model=64, n_head=4, n_kv_head=2, max_seq=64),
        max_slots=2, max_seq=64, prefill_buckets=(16,), kv_block_size=16, seed=0,
    ))
    assert engine._state_arm is None
    assert "state_kernel_steps" not in engine.stats and "state_plain_steps" not in engine.stats
