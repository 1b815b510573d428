"""The output check at a size a test run can hold: the program passes, the
lower-precision control and the displaced cache do not. On the chip the same
code runs at the published widths (benchmarks/check.py)."""

import pytest

from benchmarks import check, harness


def _tiny(cell_name):
    cell = harness.cell(cell_name)
    return harness.cell_files(cell, rehearsal=1)


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 3000000011])
def test_llama_program_agrees_and_the_controls_do_not(seed):
    c, mix = _tiny("serve-code-mistral7b")
    program = check.check_one(c, mix, seed, "program")["logits_rel_err"]
    fp8 = check.check_one(c, mix, seed, "fp8")["logits_rel_err"]
    displaced = check.check_one(c, mix, seed, "displaced")["logits_rel_err"]
    assert program < 0.02
    assert fp8 > 3 * program
    assert displaced > 10 * program


@pytest.mark.parametrize("seed", [1, 3000000012])
def test_gpt2_program_agrees_and_the_control_does_not(seed):
    c, job = _tiny("train-gpt2xl-fsdp4")
    program = check.check_one(c, job, seed, "program")
    fp8 = check.check_one(c, job, seed, "fp8")
    assert program["grad_rel_err"] < 0.03 and program["logits_rel_err"] < 0.03
    assert fp8["grad_rel_err"] > 3 * program["grad_rel_err"]
    assert fp8["logits_rel_err"] > 3 * program["logits_rel_err"]
    assert program["loss"][0] == pytest.approx(program["loss"][1], rel=1e-3)


def test_llama_reference_is_causal_and_matches_the_programs_forward_in_float32():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import llama_ref
    from ray_tpu.models import llama

    c, mix = _tiny("serve-code-mistral7b")
    c = {**c, "dtype": "float32", "param_dtype": "float32"}
    w = llama_ref.init_weights(3, c)
    tokens = np.random.default_rng(3).integers(0, c["vocab_size"], (2, 40)).astype(np.int32)
    ref = llama_ref.forward(w, jnp.asarray(tokens), c)
    cfg = harness.family(c).model_config(c, {"engine": {"max_seq": 64}})
    import dataclasses
    with jax.default_matmul_precision("highest"):
        got = llama.forward(w, jnp.asarray(tokens), dataclasses.replace(cfg, attn_impl="reference"))
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-4
    changed = tokens.copy()
    changed[:, 30:] = 0
    again = llama_ref.forward(w, jnp.asarray(changed), c)
    assert float(jnp.max(jnp.abs(again[:, :30] - ref[:, :30]))) == 0.0
