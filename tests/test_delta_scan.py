"""The choice between the two arms of the delta rule's chunked scan
(``ops/delta_scan.py``, ``models/paged.py:state_prefill``) and the counters
that say which a prefill program was built with. The kernel's arithmetic is
held to the recurrence beside the plain form's in ``tests/test_kimi_linear.py``
(every test of ``kda_chunked`` takes the arm as a parameter); its compiled
form for a described v5e is in ``tests/test_tpu_aot.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams
from ray_tpu.models import kimi_linear, nemotron_h, paged, solar_open2
from ray_tpu.ops import delta_scan

SERVED = {"serve-longdoc-solaropen2": 64, "serve-batch-kimilinear": 32}


@pytest.mark.parametrize("cell", sorted(SERVED))
def test_fits_is_decided_by_platform_mesh_and_shapes(cell, monkeypatch):
    """The served heads tile from 2,048 rows a program (a smaller bucket does
    not pay for the kernel's lowering at every start); here, on the CPU,
    nothing fits; on a TPU they do, but not under a mesh of two chips (the
    compiler cannot partition a Mosaic call), and not at a width that is not a
    lane tile. No argument and no environment variable says which."""
    H = SERVED[cell]
    assert delta_scan.tiles(2048, H, 128, 128) and delta_scan.head_group(H) == 4
    assert delta_scan.tiles(4096, H, 128, 128) and not delta_scan.tiles(1024, H, 128, 128)
    assert not delta_scan.tiles(2048, H, 64, 128) and not delta_scan.tiles(2048, H, 128, 256)
    assert jax.default_backend() == "cpu" and not delta_scan.fits(2048, H, 128, 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert delta_scan.fits(2048, H, 128, 128)
    assert delta_scan.fits(2048, H, 128, 128, Mesh(np.array(jax.devices()[:1]), ("tp",)))
    assert not delta_scan.fits(2048, H, 128, 128, Mesh(np.array(jax.devices()[:2]), ("tp",)))
    state = jax.ShapeDtypeStruct((3, 33, H, 128, 128), jnp.float32)
    assert paged.prefill_scans_in_kernel(state, 2048) and not paged.prefill_scans_in_kernel(state, 512)
    assert not paged.prefill_scans_in_kernel(jax.ShapeDtypeStruct((3, 33, H, 16, 16), jnp.float32), 2048)


@pytest.mark.parametrize("heads, group", [(64, 4), (32, 4), (6, 3), (7, 1), (3, 3)])
def test_a_grid_step_holds_a_count_of_heads_that_divides_them(heads, group):
    assert delta_scan.head_group(heads) == group


def _engine(config, **kw):
    return LLMEngine(LLMConfig(
        model_config=config.tiny(max_seq=128), max_slots=2, max_seq=128, prefill_buckets=(16, 64),
        kv_block_size=16, seed=0, enable_prefix_caching=False, **kw,
    ))


@pytest.mark.parametrize("family", ["kimi_linear", "solar_open2"])
def test_the_engine_counts_its_prefill_programs_by_the_arm_of_their_scan(family, monkeypatch):
    """``prefill_scan_kernel_runs`` / ``prefill_scan_plain_runs``: every
    prefill or chunk program a delta-rule family launches, by what
    ``paged.prefill_scans_in_kernel`` says of the pool's state and the
    launch's bucket: here, on the CPU, the plain loop."""
    config, chunks = {
        "kimi_linear": (kimi_linear.KimiLinearConfig, {}),
        "solar_open2": (solar_open2.SolarOpen2Config, {"prefill_chunk_tokens": 16}),
    }[family]
    engine = _engine(config, **chunks)
    assert engine.stats["prefill_scan_kernel_runs"] == engine.stats["prefill_scan_plain_runs"] == 0
    tokens = np.random.default_rng(0).integers(3, 200, size=40).tolist()
    engine.generate([tokens, tokens[:9]], SamplingParams(max_tokens=2))
    launched = 4 if chunks else 2  # 40 tokens in chunks of 16 and 9 in one, or a bucket a prompt
    assert engine.stats["prefill_scan_plain_runs"] == launched
    assert engine.stats["prefill_scan_kernel_runs"] == 0
    # what the engine asks is paged's one function, of each launch's bucket
    asked = []
    monkeypatch.setattr(
        paged, "prefill_scans_in_kernel",
        lambda state, tokens, mesh=None: asked.append((state.shape, tokens)) or tokens == 16,
    )
    engine.generate([tokens[:9], tokens], SamplingParams(max_tokens=2))
    shape = engine.pool["state"].shape
    assert asked == [(shape, 16)] * 4 if chunks else sorted(asked) == [(shape, 16), (shape, 64)]
    assert engine.stats["prefill_scan_kernel_runs"] == (4 if chunks else 1)
    assert engine.stats["prefill_scan_plain_runs"] == launched + (0 if chunks else 1)


def test_a_family_whose_state_is_no_delta_rules_counts_neither():
    engine = _engine(nemotron_h.NemotronHConfig)
    assert paged.cache(engine.model_config).slot_state and not paged.cache(engine.model_config).delta_rule
    assert not [k for k in engine.stats if k.startswith("prefill_scan_")]


def test_state_prefill_hands_the_mixer_a_held_state_only_where_the_kernel_runs():
    """``paged.state_prefill`` given ``scan_rows`` (a delta rule's state):
    the step is given the state held for the kernel under ``interpret``
    (whatever the shapes) and in the TPU's branch at the kernel's rows and
    widths, an array elsewhere; a family that gives no rows is always given
    the array."""
    seen = []

    def step(state0, tail0):
        seen.append(type(state0))
        state0 = state0.state if isinstance(state0, delta_scan.Held) else state0
        return jnp.zeros((4, 8)), state0 + 1.0, tail0

    def run(d, traced=False, **kw):
        seen.clear()
        state, conv = jnp.zeros((2, 3, 2, d, d)), jnp.zeros((2, 3, 3, 6))
        prefill = lambda state, conv: paged.state_prefill(  # noqa: E731
            step, state, conv, 1, None, jnp.bool_(True), **kw
        )
        _, state, _ = (jax.jit(prefill) if traced else prefill)(state, conv)
        assert float(state[1, 2].min()) == 1.0 and float(jnp.abs(state[0]).max()) == 0.0
        return list(seen)

    assert delta_scan.Held not in run(16)
    assert run(16, scan_rows=64, interpret=True) == [delta_scan.Held]
    assert delta_scan.Held not in run(16, scan_rows=2048)  # no lane tile: the plain loop alone
    assert delta_scan.Held not in run(128, interpret=True)  # not a delta rule's state
    assert delta_scan.Held not in run(128, scan_rows=2048)  # run here, on the CPU
    assert delta_scan.Held not in run(128, traced=True, scan_rows=1024)  # too few rows to pay
    both = run(128, traced=True, scan_rows=2048)  # the two branches of a choice made at lowering
    assert len(both) == 2 and both.count(delta_scan.Held) == 1
