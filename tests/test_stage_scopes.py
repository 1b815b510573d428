"""The stages inside the device programs (``models/common.py:stage``): every
family's prefill and decode program and the GPT-2 train step, at their tiny
sizes on the CPU, carry one vocabulary in the ``op_name`` of their HLO
metadata, and the scopes change no instruction of the program.
"""

import contextlib
import importlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax._src.lib import xla_client

from ray_tpu.models import common, paged
from ray_tpu.models.common import STAGES
from ray_tpu.train.spmd import default_optimizer, make_train_step

pytestmark = pytest.mark.timeout(600)

ATTENTION = {"attn_proj", "attn_core", "pool_write", "embed_head"}
STATE = {"state_in", "state_scan", "state_out"}
EXPERTS = {"router", "experts"}
# family -> (module, configuration class, the stages its two programs have at the tiny size)
FAMILIES = {
    "gpt2": ("gpt2", "GPT2Config", ATTENTION | {"mlp"}),
    "llama": ("llama", "LlamaConfig", ATTENTION | {"mlp"}),
    "kimi_linear": ("kimi_linear", "KimiLinearConfig", ATTENTION | STATE | EXPERTS | {"mlp"}),
    "mla_moe": ("mla_moe", "MlaMoeConfig", ATTENTION | EXPERTS | {"mlp"}),
    "nemotron_h": ("nemotron_h", "NemotronHConfig", ATTENTION | STATE | EXPERTS),
    "afmoe": ("afmoe", "AfmoeConfig", ATTENTION | EXPERTS | {"mlp"}),
    "solar_open2": ("solar_open2", "SolarOpen2Config", ATTENTION | STATE | EXPERTS),
    "mimo_v2": ("mimo_v2", "MimoV2Config", ATTENTION | EXPERTS | {"mlp"}),
    "granitemoehybrid": ("granite_hybrid", "GraniteHybridConfig", ATTENTION | STATE | {"mlp"}),
    "deepseek_v32": ("deepseek_v32", "DeepseekV32Config", ATTENTION | EXPERTS | {"mlp", "attn_select"}),
}
TRAIN_STAGES = {"attn_proj", "attn_core", "mlp", "embed_head", "optimizer"}
# The operations that do a program's work: each must say which stage it is.
WORK = ("dot", "convolution", "scatter", "sort", "custom-call")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([\w\-]+)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
STAGE = re.compile(r"\bst\.(\w+)")


def lower_engine(family: str, program: str):
    module, config, _ = FAMILIES[family]
    mod = importlib.import_module(f"ray_tpu.models.{module}")
    cfg = getattr(mod, config).tiny()
    bs, width, slots = 16, 8, 4
    shapes = jax.eval_shape(
        lambda key: (mod.init_params(key, cfg), paged.init_block_pool(cfg, width * slots + 1, bs, slots)),
        jax.random.PRNGKey(0),
    )
    table = jnp.arange(1, width + 1, dtype=jnp.int32)
    if len(paged.cache(cfg).retention) > 1:  # a block table a layer kind
        table = jnp.stack([table, table])
    if program == "prefill":
        def run(params, pool, tokens):
            return paged.paged_prefill(
                params, tokens, jnp.int32(20), jnp.int32(0), table, pool, cfg, block_size=bs, slot=jnp.int32(1)
            )
        operand = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    else:
        def run(params, pool, tokens):
            return paged.paged_decode(
                params, tokens, jnp.full(slots, 5, jnp.int32), jnp.stack([table] * slots), pool, cfg,
                block_size=bs, live=jnp.ones(slots, bool),
            )
        operand = jax.ShapeDtypeStruct((slots,), jnp.int32)
    return jax.jit(run).lower(*shapes, operand)


def lower_train(_family, _program):
    from ray_tpu.models import gpt2

    cfg = gpt2.GPT2Config.tiny()
    opt = default_optimizer()
    params = jax.eval_shape(lambda key: gpt2.init_params(key, cfg), jax.random.PRNGKey(0))
    state = {
        "params": params, "opt_state": jax.eval_shape(opt.init, params),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    step = make_train_step(lambda p, b: gpt2.loss_fn(p, b, cfg), opt, donate_state=False)
    return step.lower(state, {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)})


def fingerprint(lowered) -> str:
    """The program as it is handed to the compiler, without metadata and with
    canonical names: equal for two programs of the same instructions."""
    options = xla_client._xla.HloPrintOptions.fingerprint()
    return lowered.compiler_ir(dialect="hlo").as_hlo_module().to_string(options)


CASES = [(f, p, lower_engine, FAMILIES[f][2]) for f in FAMILIES for p in ("prefill", "decode")]
CASES.append(("gpt2", "train", lower_train, TRAIN_STAGES))


@pytest.mark.parametrize("family,program,lower,expected", CASES, ids=[f"{c[0]}-{c[1]}" for c in CASES])
def test_every_working_operation_carries_one_stage_of_the_vocabulary(
    family, program, lower, expected, monkeypatch
):
    lowered = lower(family, program)
    text = lowered.compile().as_text()
    seen, backward, unsaid = set(), set(), []
    for line in text.splitlines():
        found = INSTRUCTION.match(line)
        if not found:
            continue
        name = OP_NAME.search(line)
        stages = set(STAGE.findall(name.group(1))) if name else set()
        assert stages <= set(STAGES), line  # no name outside the vocabulary
        assert not name or "granite_" not in name.group(1), line  # nor a family's own
        seen |= stages
        if name and "transpose(jvp(" in name.group(1):
            backward |= stages
        # An instruction the compiler made out of others (the CPU's rewrites
        # of a dot) carries no op_name at all: there is nothing to hold it to.
        if found.group(1) in WORK and name and len(stages) != 1:
            unsaid.append(line.strip()[:300])
    assert not unsaid, unsaid[:5]
    assert seen == expected
    if program == "train":  # the backward pass says its stages too: all but the optimizer's
        assert backward == expected - {"optimizer"}
    # Scopes are metadata: without them the program is the same instructions.
    monkeypatch.setattr(common.stage, "_recreate_cm", lambda self: contextlib.nullcontext())
    assert fingerprint(lower(family, program)) == fingerprint(lowered)


def test_a_stage_outside_the_vocabulary_is_refused():
    with pytest.raises(ValueError, match="no stage of the vocabulary"):
        common.stage("granite_mlp")


def test_the_delta_scan_kernel_is_opened_inside_the_state_scan():
    """Kimi Linear's prefill at heads of 128 x 128, lowered for a TPU from
    here: the KDA layers' scans are the call ``kda_scan``, one traced body
    that each layer calls, and the call's location carries ``st.state_scan``,
    so a device trace's time by stage reads it there."""
    from ray_tpu.models import kimi_linear as kl

    cfg = kl.KimiLinearConfig.tiny(n_layer=2, kda_head_dim=128, max_seq=2048)
    bs, width, slots = 16, 128, 2
    shapes = jax.eval_shape(
        lambda key: (kl.init_params(key, cfg), paged.init_block_pool(cfg, width * slots + 1, bs, slots)),
        jax.random.PRNGKey(0),
    )
    table = jnp.arange(1, width + 1, dtype=jnp.int32)

    def run(params, pool, tokens):
        return paged.paged_prefill(
            params, tokens, jnp.int32(1500), jnp.int32(0), table, pool, cfg, block_size=bs, slot=jnp.int32(1)
        )

    traced = jax.jit(run).trace(*shapes, jax.ShapeDtypeStruct((1, 2048), jnp.int32))
    text = traced.lower(lowering_platforms=("tpu",)).compiler_ir(dialect="hlo").as_hlo_module().to_string()
    kernels = [line for line in text.splitlines() if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(kernels) == 1 and OP_NAME.search(kernels[0]).group(1) == "kda_scan/pallas_call"
    # The compiler puts the body where it is called, under the caller's name.
    sites = [line for line in text.splitlines() if " call(" in line and "jit(_scan)" in line]
    assert len(sites) == len(cfg.kda_layers) == 2
    for line in sites:
        assert STAGE.findall(OP_NAME.search(line).group(1)) == ["state_scan"], line
