"""Compile the TPU programs for a v5e from the CPU.

``jax.experimental.topologies`` describes a 2x2 v5e host without a chip, and
XLA's TPU compiler (libtpu) runs anywhere, so the Mosaic kernels and the
sharded train step are lowered and compiled here exactly as they would be on
the host. The virtual CPU mesh cannot stand in for this: ``attn_impl="auto"``
picks the jnp reference off-TPU, so it never meets the rule that XLA does not
partition a Mosaic call.
"""

import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import gpt2
from ray_tpu.ops import attention
from ray_tpu.parallel import (
    DEFAULT_RULES,
    MeshSpec,
    make_mesh,
    shardings_from_logical,
)
from ray_tpu.train.spmd import default_optimizer, make_train_step

pytestmark = pytest.mark.timeout(600)

MOSAIC = "tpu_custom_call"


def mosaic_calls(text: str) -> list:
    """The names of a compiled program's Mosaic calls, as a device trace
    lists them, without their numbers."""
    calls = re.findall(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*custom_call_target=\"" + MOSAIC + '"',
        text, re.M,
    )
    return [re.sub(r"\.\d+$", "", c) for c in calls]


@pytest.fixture(scope="module")
def v5e_2x2():
    try:
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        ).devices
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    assert len(devices) == 4 and devices[0].device_kind == "TPU v5 lite"
    return devices


@pytest.mark.parametrize(
    "shape, block",
    [
        ((8, 12, 1024, 64), 512),  # two blocks a head, each diagonal tile in two groups
        ((2, 25, 1024, 64), 1024),  # the shard of GPT-2 XL under fsdp=4: one block a head
        ((2, 8, 4096, 64), 1024),  # the backward's full-length operands past the default VMEM scope
        ((2, 8, 1024, 128), 1024),  # Llama's head
    ],
    ids=["S1024-block512", "gpt2xl-shard", "S4096", "head128"],
)
def test_flash_kernels_compile_on_one_chip(v5e_2x2, shape, block):
    one = NamedSharding(Mesh(np.array(v5e_2x2[:1]), ("x",)), P())
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
    assert attention.diag_group(block, block) == block // 2

    def fwd(q, k, v):
        return attention.causal_attention(
            q, k, v, impl="pallas", block_q=block, block_k=block
        )

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    assert MOSAIC in jax.jit(fwd).lower(q, q, q).compile().as_text()
    bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).compile()
    assert bwd.as_text().count(MOSAIC) == 2  # forward + fused backward


@pytest.mark.parametrize("meshed", [False, True], ids=["one-chip", "fsdp4"])
def test_flash_kernels_keep_their_names_in_the_compiled_program(v5e_2x2, meshed):
    """A device trace names an operation after its HLO instruction. The
    Mosaic calls are ``flash_fwd.<n>`` and ``flash_bwd.<n>`` there, under a
    mesh too, where they used to take the ``shard_map``'s name and number."""
    devices = v5e_2x2 if meshed else v5e_2x2[:1]
    mesh = make_mesh(MeshSpec(fsdp=len(devices)), devices)
    sharding = NamedSharding(mesh, P(("dp", "fsdp")))
    q = jax.ShapeDtypeStruct((8, 12, 1024, 64), jnp.bfloat16, sharding=sharding)

    def loss(q, k, v):
        return attention.causal_attention(
            q, k, v, impl="pallas", block_q=512, block_k=512,
            mesh=mesh if meshed else None,
        ).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).compile().as_text()
    assert sorted(mosaic_calls(text)) == ["flash_bwd", "flash_fwd"]


@pytest.mark.parametrize(
    "spec", [MeshSpec(dp=4), MeshSpec(fsdp=2, tp=2)], ids=["dp4", "fsdp2-tp2"]
)
def test_gpt2_125m_train_step_compiles_on_four_chips(v5e_2x2, spec):
    """The step chip_smoke.py runs: full-width GPT-2-125M, 8 sequences per
    chip, S=1024, attn_impl="auto", loss_chunk=0, donated state."""
    cfg = dataclasses.replace(gpt2.GPT2Config.gpt2_125m(), loss_chunk=0)
    mesh = make_mesh(spec, v5e_2x2)
    shardings = shardings_from_logical(
        gpt2.param_logical_specs(cfg), DEFAULT_RULES, mesh
    )
    opt = default_optimizer(total_steps=100)
    step = make_train_step(
        lambda p, b: gpt2.loss_fn(p, b, cfg, mesh=mesh),
        opt,
        mesh=mesh,
        batch_spec=P(("dp", "fsdp")),
        param_shardings=shardings,
    )

    def init(key):
        params = gpt2.init_params(key, cfg)
        return {
            "params": params,
            "opt_state": opt.init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    def placed(tree, sharding_tree):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree,
            sharding_tree,
        )

    shapes = jax.eval_shape(init, jax.random.key(0))
    replicated = NamedSharding(mesh, P())
    state = {
        "params": placed(shapes["params"], shardings),
        "opt_state": jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=replicated
            ),
            shapes["opt_state"],
        ),
        "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated),
    }
    tokens = jax.ShapeDtypeStruct(
        (32, cfg.max_seq),
        jnp.int32,
        sharding=NamedSharding(mesh, P(("dp", "fsdp"))),
    )
    compiled = step.lower(state, {"tokens": tokens, "targets": tokens}).compile()
    # The flash kernel ran, not the reference: forward and fused backward.
    assert compiled.as_text().count(MOSAIC) == 2
    memory = compiled.memory_analysis()
    assert (
        memory.temp_size_in_bytes + memory.argument_size_in_bytes < 16e9
    ), "does not fit one v5e chip's 16 GB"


@pytest.mark.parametrize(
    "group,block,width", [(4, 16, 128), (1, 32, 64), (8, 128, 16)],
    ids=["mistral-7b", "mha-blocks-of-32", "group-8-blocks-of-128"],
)
def test_the_decode_attention_kernel_compiles_at_served_widths(
    v5e_2x2, group, block, width
):
    """The block-walking kernel at the widths the benchmark serves (sixteen
    slots, 8 KV heads of 128, tables of 2,048 positions over a 16-layer
    pool left in HBM) and at other groups and block sizes that
    ``paged_attention.fits`` admits: what the chip's compiler would refuse
    (a copy off the tiling, too much VMEM) it refuses here."""
    from ray_tpu.ops import paged_attention

    assert paged_attention.fits(8, 128, block, itemsize=2)
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    pool = sds((16, 2049 * 16 // block, 8, block, 128), jnp.bfloat16)
    compiled = jax.jit(paged_attention.paged_decode_attention).lower(
        sds((16, 8, group, 128), jnp.bfloat16), pool, pool,
        sds((), jnp.int32), sds((16, width), jnp.int32), sds((16,), jnp.int32),
    ).compile()
    assert compiled.as_text().count(MOSAIC) == 1
    # Nothing pool-sized beside the pool: the operands stay where they are.
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_the_windowed_decode_attention_kernel_compiles_at_served_widths(v5e_2x2):
    """The same kernel with its walk's lower bound, as Trinity's sliding
    layers call it: twenty-four slots, six query heads a key/value head,
    tables of 18,432 positions over a four-layer part of the pool, a window
    of 4,096."""
    import functools

    from ray_tpu.ops import paged_attention

    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    pool = sds((4, 24 * 385 + 1, 8, 16, 128), jnp.bfloat16)
    compiled = jax.jit(
        functools.partial(paged_attention.paged_decode_attention, window=4096)
    ).lower(
        sds((24, 8, 6, 128), jnp.bfloat16), pool, pool,
        sds((), jnp.int32), sds((24, 1152), jnp.int32), sds((24,), jnp.int32),
    ).compile()
    assert compiled.as_text().count(MOSAIC) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_the_latent_attention_kernel_compiles_at_served_widths(v5e_2x2):
    """The latent arm of the block-walking kernel at the widths A.X-K1 is
    served at (32 slots, a pool of 7 layers of 8,193 blocks of 16 rows of 640
    left in HBM, tables of 4,096 positions, 64 heads, values 512 wide): one
    Mosaic call under its own name, the lane slice that takes the values out
    of the rows' buffer and its chunk buffers accepted, and nothing of the
    pool's size beside the pool (the unit axis that makes the rows one "KV
    head" is a bitcast, not a copy)."""
    from ray_tpu.ops import paged_attention

    assert paged_attention.fits_latent(64, 640, 512, 16, itemsize=2)
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    compiled = jax.jit(
        functools.partial(
            paged_attention.paged_latent_decode_attention, value_width=512, scale=0.1147
        )
    ).lower(
        sds((32, 64, 640), jnp.bfloat16), sds((7, 8193, 16, 640), jnp.bfloat16),
        sds((), jnp.int32), sds((32, 256), jnp.int32), sds((32,), jnp.int32),
    ).compile()
    assert mosaic_calls(compiled.as_text()) == ["paged_latent_decode_attention"]
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_axk1s_decode_program_attends_its_latent_pool_in_place(v5e_2x2):
    """``mla_moe.paged_decode`` at A.X-K1's served shapes (the benchmark's
    configuration: 7 layers of the published widths, 12 experts held), lowered
    for the chip: seven latent kernels, one a layer, the pool donated and
    aliased, and no temporary the size of a gathered table (32 slots x 4,096
    rows x 640 lanes in bf16 is 168 MB a layer; the gathering program held
    257 MB of temporaries)."""
    from ray_tpu.models import mla_moe, paged

    cfg = mla_moe.MlaMoeConfig(
        vocab_size=20480, n_layer=7, experts_held=12, max_seq=4096
    )
    B, bs, N = 32, 16, 8193
    assert paged._latent_kernel_fits(cfg, bs, None)
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    on_chip = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params = on_chip(jax.eval_shape(lambda k: mla_moe.draw_params(k, cfg), jax.random.key(0)))
    pool = on_chip(jax.eval_shape(lambda: mla_moe.init_pool(cfg, N, bs)))
    compiled = jax.jit(
        functools.partial(paged.paged_decode, cfg=cfg, block_size=bs), donate_argnums=4
    ).lower(
        params, sds((B,), jnp.int32), sds((B,), jnp.int32),
        sds((B, cfg.max_seq // bs), jnp.int32), pool, live=sds((B,), jnp.bool_),
    ).compile()
    assert mosaic_calls(compiled.as_text()).count("paged_latent_decode_attention") == cfg.n_layer
    mem = compiled.memory_analysis()
    table_bytes = B * cfg.max_seq * cfg.pool_row_dim * 2
    assert mem.alias_size_in_bytes >= N * bs * cfg.pool_row_dim * 2 * cfg.n_layer
    assert mem.temp_size_in_bytes < table_bytes // 2
    gathered = f"bf16[{B},{cfg.max_seq // bs},{bs},{cfg.pool_row_dim}]"
    assert gathered not in compiled.as_text()


@pytest.mark.parametrize(
    "m,experts,K,N",
    [
        (64 * 22, 128, 1024, 2688), (64 * 22, 128, 2688, 1024),
        (16 * 8, 64, 2304, 1024), (2048, 64, 1024, 2304),
        (32 * 8, 12, 7168, 2048), (2048, 12, 2048, 7168),
        (32 * 8, 40, 4096, 1280), (2048, 40, 1280, 4096),
    ],
    ids=["nemotron-up", "nemotron-down", "kimi-up", "kimi-down-pass", "axk1-up", "axk1-down-pass",
         "solar-up", "solar-down-pass"],
)
def test_the_grouped_product_kernel_compiles_at_served_widths(v5e_2x2, m, experts, K, N):
    """``ops.moe_gmm.gmm`` at the three expert cells' widths, both
    directions, at a decode step's rows or a prefill pass's: its weight
    tiles, two of them in flight, fit the VMEM it asks for, the call keeps
    its name (a device trace lists it as ``moe_gmm.<n>``), and nothing of
    the weights' size is made beside them."""
    from ray_tpu.ops import moe_gmm

    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    compiled = jax.jit(moe_gmm.gmm).lower(
        sds((m, K), jnp.bfloat16), sds((experts, K, N), jnp.bfloat16), sds((experts,), jnp.int32)
    ).compile()
    assert mosaic_calls(compiled.as_text()) == ["moe_gmm"]
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("program", ["paged_prefill", "paged_decode"])
def test_engine_paged_programs_write_the_pool_in_place(v5e_2x2, program):
    """The engine's two paged programs, compiled for one chip: the pool is
    carried through the layer scan and donated, so the output aliases the
    input, nothing slab-sized is a temporary (the scan used to slice each
    layer's slab out, copy it for the scatter and write it into a stacked
    output), and the loop body holds no dynamic-update-slice of the pool."""
    from ray_tpu.llm import LLMConfig, LLMEngine
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.tiny(
        n_layer=3, d_model=256, n_head=2, n_kv_head=1, max_seq=128
    )
    eng = LLMEngine(
        LLMConfig(
            model_config=cfg, max_slots=4, max_seq=128,
            prefill_buckets=(32,), kv_block_size=16, num_kv_blocks=8193,
            seed=0,
        )
    )
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: sds(x.shape, x.dtype), tree
    )
    i32, W = jnp.int32, eng.block_tables.shape[1]
    if program == "paged_prefill":
        lowered = eng._pg_prefill.lower(  # the tokens and the one small operand
            on_chip(eng.params), sds((1, 32), i32), sds((3 + W,), i32), on_chip(eng.pool),
        )
    else:
        lowered = eng._pg_decode.lower(  # the step before's tokens, the one operand
            on_chip(eng.params), sds((4,), i32), sds((4, 4 + W), i32),
            on_chip(eng.pool),
        )
    compiled = lowered.compile()
    # Decode, lowered for the chip, attends the live blocks in place (head
    # 128 and blocks of 16 are whole tiles): the kernel, handed the carried
    # pool where it lies. Prefill gathers.
    assert compiled.as_text().count(MOSAIC) == (program == "paged_decode")
    pool_k = eng.pool["k"]
    pool_bytes = 2 * pool_k.nbytes
    slab_bytes = pool_k.nbytes // cfg.n_layer
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 2 * slab_bytes
    pool_shape = "[" + ",".join(map(str, pool_k.shape)) + "]"
    # "%name = type[shape]{layout} op(": an instruction's name and result.
    results = [ln.split("(", 1)[0] for ln in compiled.as_text().splitlines()]
    assert any(pool_shape in r for r in results)  # the pattern can match
    assert not [
        r for r in results if "dynamic-update-slice" in r and pool_shape in r
    ]


@pytest.mark.parametrize("program", ["chunk_of_2048", "decode_of_32_slots"])
def test_solar_open2s_served_programs_compile_for_one_chip(v5e_2x2, program):
    """``solar_open2``'s two programs at the benchmark's shapes (one period of
    the published widths, 40 of 320 experts held, 32 slots of 18,432
    positions), lowered for the chip: the 2,048-token chunk that continues a
    prompt (three delta-rule scans at 64 heads, each one call of the scan's
    kernel over operands that lie as the projection left them, the GQA layer's
    attention one call of the prefill kernel over the table) and the decode step (the
    attention kernel over the live blocks, once: one GQA layer; the state read
    and written by slot), the pool donated and aliased, and weights, cache
    and temporaries within the chip's 16 GB. (The experts' grouped products
    are ``ragged_dot`` in a program built in a process whose backend is the
    CPU: their kernel compiles at this family's widths in the test above.)"""
    from ray_tpu.models import paged, solar_open2 as so
    from ray_tpu.ops import delta_rule

    cfg = so.SolarOpen2Config(
        vocab_size=24576, layer_kinds=so.PUBLISHED_LAYER_KINDS[:4], experts_held=40,
        max_seq=18432, state_slots=32,
    )
    B, bs, N = 32, 16, 36865
    W = cfg.max_seq // bs
    assert paged._kernel_fits(paged.attention_kind(cfg), bs, None)
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    on_chip = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params = on_chip(jax.eval_shape(lambda k: so.draw_params(k, cfg), jax.random.key(0)))
    pool = on_chip(jax.eval_shape(lambda: so.init_pool(cfg, N, bs)))
    i32 = jnp.int32
    if program == "chunk_of_2048":
        compiled = jax.jit(
            functools.partial(paged.paged_prefill, cfg=cfg, block_size=bs), donate_argnums=5
        ).lower(
            params, sds((1, 2048), i32), sds((), i32), sds((), i32), sds((W,), i32), pool,
            slot=sds((), i32),
        ).compile()
        text = compiled.as_text()
        calls = mosaic_calls(text)
        assert "paged_decode_attention" not in calls
        # The GQA layer's attention is the prefill kernel, once, and no
        # stretch's scores are a value of the program.
        assert calls.count("paged_prefill_attention") == cfg.layers_of(so.GQA) == 1
        assert f"f32[{cfg.n_kv_head},{cfg.n_head // cfg.n_kv_head},512,512]" not in text
        # Each KDA layer's scan is the kernel (ops/delta_scan.py), once: the
        # state and a chunk's values stay on the chip, so the program holds
        # no operand re-laid head-major [n, H, C, d], no decay for every pair
        # of a chunk's positions, and no solver.
        H, C, d = cfg.kda_heads, delta_rule.CHUNK, cfg.kda_head_dim
        n = 2048 // C
        assert calls.count("kda_scan") == cfg.layers_of(so.KDA) == 3
        shapes = {tuple(map(int, s.split(","))) for s in re.findall(r"f32\[([\d,]+)\]", text)}
        assert (H, d) == (64, 128) and (2048, H * d) in shapes  # the pattern can match
        assert (n, H, C, d) not in shapes
        assert not [s for s in shapes if s[-3:] == (C, C, d) and math.prod(s) >= H * C * C * d]
        assert not re.search("triangular-solve|TriangularSolve|InvertDiagBlocks", text)
    else:
        compiled = jax.jit(
            functools.partial(paged.paged_decode, cfg=cfg, block_size=bs), donate_argnums=4
        ).lower(
            params, sds((B,), i32), sds((B,), i32), sds((B, W), i32), pool, live=sds((B,), jnp.bool_),
        ).compile()
        calls = mosaic_calls(compiled.as_text())
        assert calls.count("paged_decode_attention") == cfg.layers_of(so.GQA) == 1
        gathered = f"bf16[{B},{W},{cfg.n_kv_head},{bs},{cfg.head_dim}]"
        assert gathered not in compiled.as_text()
    mem = compiled.memory_analysis()
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))  # noqa: E731
    assert 6.6e9 < nbytes(params) < 6.7e9 and 2.8e9 < nbytes(pool) < 2.9e9
    assert mem.alias_size_in_bytes >= nbytes(pool)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    print(program, {k: getattr(mem, k + "_size_in_bytes") for k in ("temp", "argument", "output", "alias")},
          nbytes(params), nbytes(pool))


@pytest.mark.parametrize("kind", ["full", "window"])
def test_the_decode_attention_kernel_compiles_at_mimo_v25s_two_shapes(v5e_2x2, kind):
    """The block-walking kernel as MiMo-V2.5's two kinds of layer call it (32
    slots, tables of 18,432 positions): 4 key/value heads of 16 queries over a
    two-layer part, no window; 8 key/value heads of 8 queries (padded to 16)
    over a five-layer part, a window of 128 and a sink a head. Keys of 192 lie
    in rows of 256 lanes beside values of 128, each pool with a chunk buffer
    of its own width. **This is where Mosaic's refusal of 192 lanes shows**: a
    key pool with rows of 192 (which the compiler lays out in 256 lanes
    anyway) is refused for a copy that is not whole lane tiles, at no chip
    time."""
    from ray_tpu.ops import paged_attention

    layers, blocks, KH, G, window, sink = {
        "full": (2, 36865, 4, 16, None, False), "window": (5, 32 * 137 + 1, 8, 8, 128, True),
    }[kind]
    assert paged_attention.fits(KH, 256, 16, itemsize=2, value_dim=128)
    assert not paged_attention.fits(KH, 192, 16, itemsize=2, value_dim=128)
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    name = f"paged_decode_attention_{kind}"
    call = jax.jit(functools.partial(paged_attention.paged_decode_attention, window=window, name=name))

    def operands(lanes):
        args = [
            sds((32, KH, G, 192), jnp.bfloat16), sds((layers, blocks, KH, 16, lanes), jnp.bfloat16),
            sds((layers, blocks, KH, 16, 128), jnp.bfloat16), sds((), jnp.int32),
            sds((32, 1152), jnp.int32), sds((32,), jnp.int32),
        ]
        return args + [sds((KH, G), jnp.float32)] * sink

    compiled = call.lower(*operands(256)).compile()
    assert mosaic_calls(compiled.as_text()) == [name]
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20
    with pytest.raises(Exception, match="aligned to tiling"):
        call.lower(*operands(192)).compile()


@pytest.mark.parametrize("program", ["chunk_of_2048", "decode_of_32_slots"])
def test_mimo_v25s_served_programs_compile_for_one_chip(v5e_2x2, program):
    """``mimo_v2``'s two programs at the benchmark's shapes (the dense layer
    and one period of the published widths, 16 of 256 experts held, 32 slots of
    18,432 positions), lowered for the chip: the 2,048-token chunk that
    continues a prompt (attention one call of the prefill kernel a layer, the
    window layers from their sink) and the decode step, whose attention is the kernel
    in every layer, under a name a kind: twice over the full part's 4 heads,
    five times over the window part's 8, and no table gathered whole. The pool
    is donated and aliased; weights, cache and temporaries are within the
    chip's 16 GB."""
    from ray_tpu.models import mimo_v2 as mm, paged

    cfg = mm.MimoV2Config(
        vocab_size=19072, layer_pattern=(0, 1, 1, 1, 1, 1, 0), moe_layers=(0, 1, 1, 1, 1, 1, 1),
        experts_held=16, max_seq=18432, window_slots=32,
    )
    B, bs, N = 32, 16, 36865
    W = cfg.max_seq // bs
    assert all(paged._kernel_fits(kind, bs, None) for kind in paged.cache(cfg).kinds)
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    on_chip = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params = on_chip(jax.eval_shape(lambda k: mm.draw_params(k, cfg), jax.random.key(0)))
    pool = on_chip(jax.eval_shape(lambda: mm.init_pool(cfg, N, bs)))
    i32 = jnp.int32
    if program == "chunk_of_2048":
        compiled = jax.jit(
            functools.partial(paged.paged_prefill, cfg=cfg, block_size=bs), donate_argnums=5
        ).lower(
            params, sds((1, 2048), i32), sds((), i32), sds((), i32), sds((2, W), i32), pool,
        ).compile()
        text = compiled.as_text()
        calls = mosaic_calls(text)
        assert not [c for c in calls if c.startswith("paged_decode_attention")]
        # A layer's attention is one call of the prefill kernel under its
        # kind's name: no stretch's scores are a value of the program ...
        assert calls.count("paged_prefill_attention_full") == 2
        assert calls.count("paged_prefill_attention_window") == 5
        assert "f32[4,16,512,512]" not in text and "f32[8,8,512,512]" not in text
        # ... and each layer's query projection is formed once (the parent's
        # compiler formed six of the seven twice: PERF.md section 6, PR 50).
        products = re.findall(r"%([\w.\-]+) = bf16\[2048,12288\]\S* fusion\(", text)
        assert len(products) == 7 and not [name for name in products if "remat" in name]
        # The chunk's keys and values go into the pool a block an update (PR
        # 52): two scatters a layer whose window is a block's [KH, 16, lanes],
        # into the donated pool where it lies; no part of the pool is copied.
        # (Fifteen: the compiler forms the first window layer's values' write
        # twice over the same input, as it did the parent's row scatter.)
        part = r"= bf16\[[25],\d+,[48],16,(?:128|256)\]\S* "
        writes = re.findall(part + r"scatter\(.*update_window_dims=\{1,2,3\}, inserted_window_dims=\{0,1\}", text)
        assert len(writes) in (14, 15) and " scatter(" not in re.sub(part + r"scatter\(", "", text)
        assert not re.findall(part + r"copy\(", text)
    else:
        compiled = jax.jit(
            functools.partial(paged.paged_decode, cfg=cfg, block_size=bs), donate_argnums=4
        ).lower(
            params, sds((B,), i32), sds((B,), i32), sds((B, 2, W), i32), pool, live=sds((B,), jnp.bool_),
        ).compile()
        calls = mosaic_calls(compiled.as_text())
        assert calls.count("paged_decode_attention_full") == 2
        assert calls.count("paged_decode_attention_window") == 5
        for heads in (4, 8):  # no table brought back whole
            assert f"bf16[{B},{W},{heads},{bs}," not in compiled.as_text()
    mem = compiled.memory_analysis()
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))  # noqa: E731
    assert 6.8e9 < nbytes(params) < 6.9e9 and 5.7e9 < nbytes(pool) < 5.8e9
    assert mem.alias_size_in_bytes >= nbytes(pool)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    print(program, {k: getattr(mem, k + "_size_in_bytes") for k in ("temp", "argument", "output", "alias")},
          nbytes(params), nbytes(pool))


def test_trinitys_chunk_program_attends_through_the_prefill_kernel(v5e_2x2):
    """``afmoe``'s 2,048-token chunk at the benchmark's shapes (the last
    dense layer and one period of the published widths, 32 of 256 experts
    held, 24 slots of 18,432 positions), lowered for the chip: the full
    layer's attention is one call of the prefill kernel, 6 queries a
    key/value head; the four layers with a window of 4,096, two chunks long,
    keep the fold by the kernel's rule over a kind's shapes
    (``ops.paged_prefill_attention.fits``), their stretches' scores values of
    the program as they were."""
    from ray_tpu.models import afmoe, paged

    S, F = afmoe.SLIDING, afmoe.FULL
    cfg = afmoe.AfmoeConfig(
        vocab_size=25024, layer_types=(S, S, F, S, S), n_dense=1, experts_held=32, max_seq=18432,
        window_slots=24,
    )
    bs, N = 16, 24577
    W = cfg.max_seq // bs
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    on_chip = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params = on_chip(jax.eval_shape(lambda k: afmoe.draw_params(k, cfg), jax.random.key(0)))
    pool = on_chip(jax.eval_shape(lambda: afmoe.init_pool(cfg, N, bs)))
    i32 = jnp.int32
    compiled = jax.jit(
        functools.partial(paged.paged_prefill, cfg=cfg, block_size=bs), donate_argnums=5
    ).lower(
        params, sds((1, 2048), i32), sds((), i32), sds((), i32), sds((2, W), i32), pool,
    ).compile()
    text = compiled.as_text()
    assert mosaic_calls(text).count("paged_prefill_attention") == cfg.layers_of(F) == 1
    assert "f32[8,6,512,512]" in text and cfg.layers_of(S) == 4
    mem = compiled.memory_analysis()
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))  # noqa: E731
    assert mem.alias_size_in_bytes >= nbytes(pool)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9


# The kinds of attention layer of the three cells that prefill in chunks:
# layers of the part, its blocks, key/value heads, queries a head, a key's
# width and its row's, window, sink, table.
_PREFILL_KINDS = {
    "mimo-v2.5 full": (2, 36865, 4, 16, 192, 256, None, False, 1152),
    "mimo-v2.5 window": (5, 32 * 137 + 1, 8, 8, 192, 256, 128, True, 1152),
    "solar-open2": (1, 36865, 8, 8, 128, 128, None, False, 1152),
    "trinity full": (1, 24577, 8, 6, 128, 128, None, False, 1152),
    "trinity window": (4, 24 * 385 + 1, 8, 6, 128, 128, 4096, False, 1152),
}


@pytest.mark.parametrize("kind", sorted(_PREFILL_KINDS))
def test_the_prefill_attention_kernel_compiles_at_the_chunked_cells_shapes(v5e_2x2, kind):
    """The kernel alone, a chunk of 2,048 queries against each kind's part
    of the pool: one Mosaic call under the name it was given, and nothing
    beside it but the queries scaled and re-laid (a KV head's query heads as
    rows) and, for keys of 192, padded to their rows' 256 lanes: 64 MB each
    at 64 heads."""
    from ray_tpu.ops import paged_prefill_attention as ppa

    layers, blocks, KH, G, Dk, lanes, window, sink, W = _PREFILL_KINDS[kind]
    # (Trinity's window kind keeps the fold in its program, by the rule over a window; the kernel takes its shapes all the same.)
    assert ppa.fits(2048, G, lanes, 128, 16, window) == (kind != "trinity window")
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    name = "paged_prefill_attention_" + kind.split()[-1]
    call = jax.jit(functools.partial(ppa.paged_prefill_attention, window=window, name=name))
    args = [
        sds((2048, KH, G, Dk), jnp.bfloat16), sds((layers, blocks, KH, 16, lanes), jnp.bfloat16),
        sds((layers, blocks, KH, 16, 128), jnp.bfloat16), sds((), jnp.int32), sds((W,), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32),
    ] + [sds((KH, G), jnp.float32)] * sink
    compiled = call.lower(*args).compile()
    assert mosaic_calls(compiled.as_text()) == [name]
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * 2048 * KH * G * lanes * 2 + 2**20


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _pallas_calls(inner)


def _plain_decode_kernel_calls(jaxpr, head_dim) -> int:
    """How many calls of the decode kernel ``jaxpr`` makes, each held to what
    the kernel was before a sink, a width a pool and a kind's name: its own
    name, six operands (three scalars ahead, the queries, two pools: no
    sink), both chunk buffers and the output of the one width."""
    calls = list(_pallas_calls(jaxpr))
    for eqn in calls:
        assert eqn.params["name"] == "paged_decode_attention"
        shapes = [v.aval.shape for v in eqn.invars]
        assert len(shapes) == 6 and shapes[4] == shapes[5] and shapes[4][-1] == head_dim
        buffers = [v.aval.shape for v in eqn.params["jaxpr"].invars if "vmem" in str(v.aval)]
        assert len(buffers) == 2 and buffers[0] == buffers[1] and buffers[0][-1] == head_dim
        assert eqn.params["out_avals"][0].shape[-1] == head_dim
    return len(calls)


@pytest.mark.parametrize(
    "slots,group,blocks,layers,width,window",
    [(16, 4, 2049, 16, 128, None), (24, 6, 9241, 4, 1152, 4096)], ids=["mistral-7b", "trinity-window"],
)
def test_a_kind_with_one_width_and_no_sink_is_the_plain_kernel_and_the_plain_gather(
    slots, group, blocks, layers, width, window
):
    """What is asked of the attention functions only where a family asks for
    it stays out of every other family's program: at Mistral's and Trinity's
    served shapes, the kind a family of one head shape states
    (``paged.attention_kind``: no sink, one width, no name) is served by the
    kernel and the gather called as before kinds existed (the window and
    nothing else bound to them); that kernel has no sink operand, one buffer
    width and its own name; that gather appends no column and pads no
    query."""
    from ray_tpu.models import llama, paged
    from ray_tpu.ops import paged_attention as pa

    def unbound(f):
        bound = {}
        while isinstance(f, functools.partial):
            assert not f.args
            bound.update(f.keywords)
            f = f.func
        return f, bound

    sds, i32, bf16 = jax.ShapeDtypeStruct, jnp.int32, jnp.bfloat16
    pool = sds((layers, blocks, 8, 16, 128), bf16)
    args = (sds((slots, 8, group, 128), bf16), pool, pool, sds((), i32), sds((slots, width), i32),
            sds((slots,), i32))
    cfg = llama.LlamaConfig.tiny(n_head=8 * group, n_kv_head=8, d_model=8 * group * 128)  # bf16 activations
    kind = paged.attention_kind(cfg, window)
    assert (kind.kv_heads, kind.key_width, kind.value_width, kind.sink, kind.key_lanes, kind.name) == (
        8, 128, 128, False, None, "")
    windowed = {} if window is None else {"window": window}
    assert unbound(paged.decode_attention(kind, 16, None, True)) == (
        pa.paged_decode_attention, {"interpret": True, **windowed})
    two_chips = Mesh(np.array(jax.devices()[:2]), ("tp",))  # no kernel under a mesh: the gather itself
    assert unbound(paged.decode_attention(kind, 16, two_chips, False)) == (paged._attend_gathered, windowed)
    dispatch, arms = unbound(paged.decode_attention(kind, 16, None, False))
    assert dispatch is jax.lax.platform_dependent and sorted(arms) == ["default", "tpu"]
    assert unbound(arms["tpu"]) == (pa.paged_decode_attention, windowed)
    assert unbound(arms["default"]) == (paged._attend_gathered, windowed)
    kernel = functools.partial(pa.paged_decode_attention, interpret=True, **windowed)
    assert _plain_decode_kernel_calls(jax.make_jaxpr(kernel)(*args).jaxpr, 128) == 1
    text = str(jax.make_jaxpr(functools.partial(paged._attend_gathered, **windowed))(*args))
    assert f",{width * 16 + 1}]" not in text and " pad[" not in text  # no column beside the keys', no padded query


def test_the_other_families_decode_programs_call_the_plain_kernel():
    """Llama's, Trinity's, Nemotron's and Solar's decode programs whole, at
    tiny sizes under the interpreted kernel: every attention layer's call is
    the plain kernel's (its own name, no sink operand, one width)."""
    from ray_tpu.models import afmoe, llama, nemotron_h, paged, solar_open2

    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    cfgs = [
        llama.LlamaConfig.tiny(n_layer=2, d_model=64, n_head=4, n_kv_head=2, max_seq=64),
        afmoe.AfmoeConfig.tiny(max_seq=64),
        nemotron_h.NemotronHConfig.tiny(max_seq=64),
        solar_open2.SolarOpen2Config.tiny(max_seq=64),
    ]
    B, bs, W, N = 2, 4, 16, 33
    for cfg in cfgs:
        mod = paged.family(cfg)
        params = jax.eval_shape(lambda k, mod=mod, cfg=cfg: mod.init_params(k, cfg), jax.random.key(0))
        pool = jax.eval_shape(lambda cfg=cfg: paged.init_block_pool(cfg, N, bs, B))
        jaxpr = jax.make_jaxpr(
            functools.partial(paged.paged_decode, cfg=cfg, block_size=bs, interpret=True))(
            params, sds((B,), i32), sds((B,), i32), sds((B, W), i32), pool)
        assert _plain_decode_kernel_calls(jaxpr.jaxpr, cfg.head_dim) >= 1, cfg.family


# Layers, rows, heads, tile and the groups of B and C (None: KDA) of the three
# cells that keep a state a slot.
_STATE_SHAPES = {
    "kimi-linear": (7, 16, 32, (128, 128), None),
    "solar-open2": (3, 32, 64, (128, 128), None),
    "nemotron-3-super": (5, 64, 128, (64, 128), 8),
}


@pytest.mark.parametrize("cell", sorted(_STATE_SHAPES))
def test_the_state_step_kernel_compiles_at_served_shapes(v5e_2x2, cell):
    """``ops.state_step`` at the three served states (32 and 64 heads of 128 x
    128 over 16 and 32 rows; 128 heads of 64 x 128 over 64 rows with ``B``,
    ``C`` by group), lowered for the chip: its blocks and its transposition
    are whole tiles and fit the VMEM it asks for, the call keeps its name (a
    device trace lists it as ``state_step_kda.<n>`` / ``state_step_ssd.<n>``),
    the state donated is the state returned, and nothing of a row's size is
    made beside it."""
    from ray_tpu.ops import state_step

    layers, rows, H, (a, b), groups = _STATE_SHAPES[cell]
    assert state_step.tiles(H, a, b)
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    state = sds((layers, rows + 1, H, a, b))
    if groups is None:
        step, name = state_step.kda, "state_step_kda"
        operands = (*[sds((rows, H, a))] * 2, sds((rows, H, b)), sds((rows, H, a)), sds((rows, H)))
    else:
        step, name = state_step.ssd, "state_step_ssd"
        operands = (sds((rows, H, a)), sds((rows, H)), sds((H,)), *[sds((rows, groups, b))] * 2, sds((H,)))

    def run(state, keep, *operands):
        out, held = step(*operands, state_step.Rows(state, layers - 1, rows, keep))
        return out, held.state

    compiled = jax.jit(run, donate_argnums=0).lower(state, sds((rows,), jnp.bool_), *operands).compile()
    assert mosaic_calls(compiled.as_text()) == [name]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= math.prod(state.shape) * 4
    assert mem.temp_size_in_bytes < 2**20


@pytest.mark.parametrize("tokens, heads", [(2048, 64), (2048, 32), (4096, 32)])
def test_the_delta_scan_kernel_compiles_at_served_shapes(v5e_2x2, tokens, heads):
    """``ops.delta_scan`` at the two KDA cells' heads (64 and 32 of 128 x 128)
    over a chunk of 2,048 tokens, the smallest program that takes it, and twice that, lowered
    for the chip: its blocks are whole tiles, what it keeps in VMEM (the
    blocks twice over, the state, the scratch) is under the limit it asks
    for, the call keeps its name (a device trace lists it as
    ``kda_scan.<n>``), and the operands go in as they lie, ``[T, H d]``: the
    program beside the call holds nothing re-laid head-major."""
    from ray_tpu.ops import delta_rule, delta_scan

    d = 128
    assert delta_scan.tiles(tokens, heads, d, d) and not delta_scan.tiles(1024, heads, d, d)
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)  # noqa: E731
    flat = sds((tokens, heads * d))
    compiled = jax.jit(functools.partial(delta_scan._scan, interpret=False)).lower(
        flat, flat, flat, flat, sds((tokens, heads)), sds((heads, d, d))
    ).compile()
    text = compiled.as_text()
    assert mosaic_calls(text) == ["kda_scan"]
    G, C = delta_scan.head_group(heads), delta_rule.CHUNK
    held = 4 * G * (2 * 5 * C * d + 2 * 2 * d * d + d * d + 6 * C * d)
    assert G == 4 and held < delta_scan._VMEM_LIMIT_BYTES // 8
    assert f"f32[{tokens // C},{heads},{C},{d}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("bucket", [2048, 1024, 512])
def test_kimi_linears_prefill_program_scans_through_the_kernel(v5e_2x2, bucket):
    """Kimi Linear's prefill at the benchmark's shapes, the three buckets its
    prompts fall in, lowered for the chip: the scan's kernel once a KDA layer
    in the largest, and the plain loop alone in the two below it, which would
    not pay for the kernel's lowering at every start."""
    from benchmarks import harness
    from ray_tpu.models import paged

    found = harness.cell("serve-batch-kimilinear")
    c, mix = harness.config_of(found), harness.traffic_of(found)
    e = mix["engine"]
    assert bucket in e["prefill_buckets"]
    cfg = harness.family(c).model_config(c, mix)
    bs, N, B = e["kv_block_size"], e["num_kv_blocks"], e["max_slots"]
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    on_chip = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    mod = paged.family(cfg)
    params = on_chip(jax.eval_shape(lambda k: mod.init_params(k, cfg), jax.random.key(0)))
    pool = on_chip(jax.eval_shape(lambda: paged.init_block_pool(cfg, N, bs, B)))
    i32 = jnp.int32
    compiled = jax.jit(
        functools.partial(paged.paged_prefill, cfg=cfg, block_size=bs), donate_argnums=5
    ).lower(
        params, sds((1, bucket), i32), sds((), i32), sds((), i32), sds((e["max_seq"] // bs,), i32), pool,
        slot=sds((), i32),
    ).compile()
    scans = mosaic_calls(compiled.as_text()).count("kda_scan")
    assert len(cfg.kda_layers) == 7 and scans == (7 if bucket >= 2048 else 0)


@pytest.mark.parametrize(
    "cell", ["serve-batch-kimilinear", "serve-longdoc-solaropen2", "serve-chat-nemotron3super"]
)
def test_a_state_familys_decode_program_steps_its_state_where_it_lies(v5e_2x2, cell):
    """The decode program of each cell whose family keeps a state a slot, its
    model built as the benchmark builds it, lowered for the chip: the state
    kernel once a state layer, the pool donated and aliased, and no value of
    the rows' shape ``[rows, H, a, b]`` or of the state's anywhere: no slice
    of the rows is brought out, no copy of the state made, and the rows are
    not set back by a dynamic-update-slice."""
    from benchmarks import harness
    from ray_tpu.models import paged

    found = harness.cell(cell)
    c, mix = harness.config_of(found), harness.traffic_of(found)
    e = mix["engine"]
    cfg = harness.family(c).model_config(c, mix)
    bs, N, B = e["kv_block_size"], e["num_kv_blocks"], e["max_slots"]
    W = e["max_seq"] // bs
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    on_chip = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    mod = paged.family(cfg)
    params = on_chip(jax.eval_shape(lambda k: mod.init_params(k, cfg), jax.random.key(0)))
    pool = on_chip(jax.eval_shape(lambda: paged.init_block_pool(cfg, N, bs, B)))
    state = pool["state"]
    assert state.shape[1] == B + 1 and paged.cache(cfg).slot_state
    i32 = jnp.int32
    compiled = jax.jit(
        functools.partial(paged.paged_decode, cfg=cfg, block_size=bs), donate_argnums=4
    ).lower(
        params, sds((B,), i32), sds((B,), i32), sds((B, W), i32), pool, live=sds((B,), jnp.bool_)
    ).compile()
    text = compiled.as_text()
    calls = mosaic_calls(text)
    steps = [name for name in calls if name.startswith("state_step_")]
    assert len(steps) == state.shape[0] and len(set(steps)) == 1
    shape = lambda dims: "f32[" + ",".join(map(str, dims)) + "]"  # noqa: E731
    # "%name = type[shape]{layout} op(": an instruction's name and result.
    results = [ln.split("(", 1)[0] for ln in text.splitlines() if " = " in ln]
    whole = [r for r in results if shape(state.shape) in r]
    assert whole  # the pattern can match: the kernel's own result is the state
    assert not [r for r in whole if any(op in r for op in (" copy", "fusion", "dynamic-update-slice"))]
    assert not [r for r in results if shape((B, *state.shape[2:])) in r]
    mem = compiled.memory_analysis()
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))  # noqa: E731
    assert mem.alias_size_in_bytes >= nbytes(pool)
    assert mem.temp_size_in_bytes < math.prod(state.shape) * 4  # (Kimi Linear's holds two copies of its latent pool)


@pytest.mark.parametrize("family", ["llama", "gpt2", "mla_moe", "afmoe", "mimo_v2"])
def test_a_family_with_no_state_a_slot_never_meets_the_state_step(family):
    """The five families that keep no state a slot: their record says so,
    their pool has no such part, and their decode program, traced whole at a
    tiny size, calls no kernel but the attention's and chooses by platform
    nowhere but there."""
    from ray_tpu.models import paged

    mod = paged.family(type("Named", (), {"family": family})())
    config = next(v for k, v in vars(mod).items() if k.endswith("Config") and hasattr(v, "tiny"))
    cfg = config.tiny(max_seq=64)
    assert not paged.cache(cfg).slot_state
    B, bs, W, N = 2, 4, 16, 33
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    params = jax.eval_shape(lambda k: mod.init_params(k, cfg), jax.random.key(0))
    pool = jax.eval_shape(lambda: paged.init_block_pool(cfg, N, bs, B))
    assert "state" not in pool and "conv" not in pool
    jaxpr = jax.make_jaxpr(functools.partial(paged.paged_decode, cfg=cfg, block_size=bs))(
        params, sds((B,), i32), sds((B,), i32), sds((B, W), i32), pool)
    names = [eqn.params["name"] for eqn in _pallas_calls(jaxpr.jaxpr)]
    assert not [n for n in names if not n.startswith("paged_")]
    assert "state_step" not in str(jaxpr)


# ---------------------------------------------------------------------------
# Granite-4.0-H-Micro (PR 51): heads of 64 attended in place, a period scanned


def test_the_packed_decode_kernel_compiles_at_granites_shape(v5e_2x2):
    """``paged_packed_decode_attention`` at the cell's shape (64 slots, 8 KV
    heads of 4 queries, heads of 64 whose value and key share a pool row of 128
    lanes, a four-layer part of 8,193 blocks, the published multiplier for a
    scale), lowered for the chip: Mosaic takes the layout, the call keeps a
    name the readers' prefix matches, and nothing of the pool's size is made
    beside it."""
    from ray_tpu.ops import paged_attention as pa

    B, KH, G, Dh, bs, W, L, N = 64, 8, 4, 64, 16, 128, 4, 8193
    assert pa.fits_packed(KH, Dh, bs, 2) and not pa.fits(KH, Dh, bs, 2)
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    call = jax.jit(functools.partial(pa.paged_packed_decode_attention, scale=1 / 64))
    compiled = call.lower(
        sds((B, KH, G, Dh), jnp.bfloat16), sds((L, N, KH, bs, 2 * Dh), jnp.bfloat16), sds((), jnp.int32),
        sds((B, W), jnp.int32), sds((B,), jnp.int32),
    ).compile()
    assert mosaic_calls(compiled.as_text()) == ["paged_decode_attention_packed"]
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("program", ["decode_of_64_slots", "prefill_of_512"])
def test_granite_hybrids_served_programs_compile_for_one_chip(v5e_2x2, program):
    """``granite_hybrid``'s two programs with the model built as the benchmark
    builds it (all 40 layers at the published widths, 64 slots of 2,048
    positions), compiled for the chip: the decode program holds ONE period's
    Mosaic calls (nine state steps and one attention call inside the scan's
    body, whatever the depth) and no gather of a table; the pool donated is
    the pool returned; and neither makes a temporary the size of a cache part
    (a prefill of 128 tokens or more used to want the whole ``conv`` part with
    its three rows along lanes, 2.6 GB: the tails lie flat for that)."""
    from benchmarks import harness
    from ray_tpu.models import paged

    found = harness.cell("serve-chat-granite4hmicro")
    c, mix = harness.config_of(found), harness.traffic_of(found)
    e = mix["engine"]
    cfg = harness.family(c).model_config(c, mix)
    assert cfg.n_layer == 40 and len(cfg.period) == 10 and cfg.periods == 4
    bs, N, B = e["kv_block_size"], e["num_kv_blocks"], e["max_slots"]
    W = e["max_seq"] // bs
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    on_chip = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    mod = paged.family(cfg)
    params = on_chip(jax.eval_shape(lambda k: mod.init_params(k, cfg), jax.random.key(0)))
    pool = on_chip(jax.eval_shape(lambda: paged.init_block_pool(cfg, N, bs, B)))
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))  # noqa: E731
    assert 6.3e9 < nbytes(params) < 6.45e9 and 6.0e9 < nbytes(pool) < 6.1e9
    assert pool["kv"].shape == (4, N, 8, bs, 128) and pool["state"].shape == (36, B + 1, 64, 64, 128)
    i32 = jnp.int32
    if program == "decode_of_64_slots":
        compiled = jax.jit(
            functools.partial(paged.paged_decode, cfg=cfg, block_size=bs), donate_argnums=4
        ).lower(
            params, sds((B,), i32), sds((B,), i32), sds((B, W), i32), pool, live=sds((B,), jnp.bool_)
        ).compile()
        calls = mosaic_calls(compiled.as_text())
        assert sorted(calls) == ["paged_decode_attention_packed"] + ["state_step_ssd"] * 9
        assert f"bf16[{B},{W},8,{bs},128]" not in compiled.as_text()  # no table gathered whole
        limit = 64 * 2**20
    else:
        compiled = jax.jit(
            functools.partial(paged.paged_prefill, cfg=cfg, block_size=bs), donate_argnums=5
        ).lower(
            params, sds((1, 512), i32), sds((), i32), sds((), i32), sds((W,), i32), pool, slot=sds((), i32)
        ).compile()
        assert mosaic_calls(compiled.as_text()) == []
        limit = 512 * 2**20
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes(pool)
    assert mem.temp_size_in_bytes < limit, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.5e9  # of the chip's 16 GB


# sha256 (16 hex digits) of each accepted cell's decode program at the commit
# before PR 51 (11518c6), which PR 52 (a prefill's block writes) left as it
# was: its lowered text with the Mosaic calls' serialized bodies cut out (a
# body carries its source's path and lines), and its jaxpr with source
# locations cut. Behind them the cell's attention layers: the largest prefill
# writes two pool tensors a layer, a block an update.
_LOWERED_AT_THE_PARENT = {
    "serve-batch-mistral7b": ("7a69735a4fd87361", "5279d828fbfe3f1a", 1),  # one body, scanned
    "serve-chat-nemotron3super": ("cfd666b203792e2c", "cc31f47ee931a05b", 1),
    "serve-longdoc-solaropen2": ("71beb01763a3e6ea", "7aac897d5b9e2c4c", 1),
    "serve-mixed-trinity": ("c6f68ddfe10caf2d", "b0ee041c9bd3b01f", 5),
    "serve-longdoc-mimov25": ("e97122a8ecb4d59b", "f23ad7ee61bc330e", 7),
}


@pytest.mark.parametrize("cell", sorted(_LOWERED_AT_THE_PARENT))
def test_an_accepted_cells_programs_lower_as_before_scales_and_packed_heads(v5e_2x2, cell):
    """Mistral's, Nemotron's, Solar's, Trinity's and MiMo's decode programs,
    built as the benchmark builds them and lowered for the chip, are what they
    were before a kind could state its scale or pack its heads and before a
    prefill wrote whole blocks: the same lowered text outside the kernels'
    bodies, and the same jaxpr, kernels' bodies included. (An edit to
    ``ops/paged_attention.py`` re-keys the Mosaic calls made from it all the
    same, by the lines a body carries: PERF.md section 6, PR 39.) The cell's
    largest prefill holds no scatter of a row of a head any more: two block
    scatters an attention layer, none told that its block ids are unique."""
    import hashlib

    from benchmarks import harness
    from ray_tpu.models import paged

    found = harness.cell(cell)
    c, mix = harness.config_of(found), harness.traffic_of(found)
    e = mix["engine"]
    cfg = harness.family(c).model_config(c, mix)
    bs, N, B = e["kv_block_size"], e["num_kv_blocks"], e["max_slots"]
    W = e["max_seq"] // bs
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    on_chip = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    mod, record = paged.family(cfg), paged.cache(cfg)
    params = on_chip(jax.eval_shape(lambda k: mod.init_params(k, cfg), jax.random.key(0)))
    pool = on_chip(jax.eval_shape(lambda: paged.init_block_pool(cfg, N, bs, B)))
    kinds, i32 = len(record.retention), jnp.int32
    tables = sds((B, W), i32) if kinds == 1 else sds((B, kinds, W), i32)
    table = sds((W,), i32) if kinds == 1 else sds((kinds, W), i32)
    live = {"live": sds((B,), jnp.bool_)} if record.slot_state else {}
    T = e.get("prefill_chunk_tokens") or max(e["prefill_buckets"])
    decode = functools.partial(paged.paged_decode, cfg=cfg, block_size=bs)
    d_args = (params, sds((B,), i32), sds((B,), i32), tables, pool)
    prefill = functools.partial(paged.paged_prefill, cfg=cfg, block_size=bs)
    p_args = (params, sds((1, T), i32), sds((), i32), sds((), i32), table, pool)

    def digest(text):
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    def outside_the_bodies(lowered):
        return digest(re.sub(r'(body\\22: \\22)[A-Za-z0-9+/=]+', r"\1", lowered.as_text()))

    *decode_at_the_parent, attention_layers = _LOWERED_AT_THE_PARENT[cell]
    got = [
        outside_the_bodies(jax.jit(decode, donate_argnums=4).lower(*d_args, **live)),
        digest(re.sub(r" at [^ \n]*:\d+", "", str(jax.make_jaxpr(decode)(*d_args, **live)))),
    ]
    assert got == decode_at_the_parent
    text = jax.jit(prefill, donate_argnums=5).lower(*p_args, slot=sds((), i32)).as_text()
    by_block = "update_window_dims = [1, 2, 3], inserted_window_dims = [0, 1], scatter_dims_to_operand_dims = [0, 1]"
    writes = [line for line in text.splitlines() if by_block in line]
    assert len(writes) == 2 * attention_layers and all("unique_indices = false" in w for w in writes)
    assert "inserted_window_dims = [0, 1, 2, 3]" not in text  # a row of a head an update


def test_the_selected_attention_kernel_compiles_at_the_served_shapes(v5e_2x2):
    """``ops.selected_attention.attend`` at DeepSeek-V3.2-Exp's chunk: 2,048
    queries of 128 heads of 192 (no whole lane tile: a head's columns are cut
    from the cell's block on the chip), ``wkvb`` as the model holds it, the
    pool and the mask left in HBM, stretches of 1,024 positions. One Mosaic
    call, and beside it the mask at a byte an entry and the queries re-laid:
    no running softmax in HBM (268 MB), no expanded keys, no scores."""
    from ray_tpu.models import deepseek_v32 as dv
    from ray_tpu.ops import selected_attention as sa

    H, T, bs, W = 128, 2048, 16, 34816 // 16
    pages = dv.KERNEL_KEY_POSITIONS // bs
    assert sa.fits(H, T, pages * bs, 128, jnp.bfloat16)
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    compiled = jax.jit(functools.partial(sa.attend, scale=0.1, nope=128, pages=pages)).lower(
        sds((T, H, 192), jnp.bfloat16), sds((512, H * 256), jnp.bfloat16), sds((6, W + 1, bs, 640), jnp.bfloat16),
        sds((), jnp.int32), sds((W,), jnp.int32), sds((T, W * bs), jnp.bool_), sds((), jnp.int32),
    ).compile()
    assert mosaic_calls(compiled.as_text()) == [sa.NAME]
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2**20


def test_deepseek_v32s_chunk_program_attends_through_the_kernel_and_its_decode_gathers(v5e_2x2):
    """The family's 2,048-token prefill and its decode step at the published
    widths, two layers deep, lowered for the chip with the pool donated: a
    layer's attention is one ``selected_attention_fold`` call that walks the
    stretches itself (no XLA loop round it: no running softmax ``[128, 2048,
    128]`` float32 and no expanded keys ``[128, 1024, 192]`` among the
    program's buffers, and fewer temporaries than the program of PR 56, which
    had both), decode has no attention kernel (rows chosen one by one), and
    both write the two pool parts in place."""
    from ray_tpu.models import deepseek_v32 as dv, paged

    cfg = dv.DeepseekV32Config(vocab_size=16160, n_layer=2, first_k_dense=1, experts_held=8, max_seq=34816)
    B, bs, N = 16, 16, 34817
    one = jax.sharding.SingleDeviceSharding(v5e_2x2[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    on_chip = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params = on_chip(jax.eval_shape(lambda k: dv.draw_params(k, cfg), jax.random.key(0)))
    pool = on_chip(jax.eval_shape(lambda: dv.init_pool(cfg, N, bs)))
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
    W = cfg.max_seq // bs
    prefill = jax.jit(functools.partial(paged.paged_prefill, cfg=cfg, block_size=bs), donate_argnums=5).lower(
        params, sds((1, 2048), jnp.int32), sds((), jnp.int32), sds((), jnp.int32), sds((W,), jnp.int32), pool,
    ).compile()
    text = prefill.as_text()
    calls = mosaic_calls(text)
    assert calls.count("selected_attention_fold") == cfg.n_layer
    assert "f32[128,2048,128]" not in text and "bf16[128,1024,192]" not in text
    mem = prefill.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 1_295_683_584  # the parent's, a loop of calls a layer
    decode = jax.jit(functools.partial(paged.paged_decode, cfg=cfg, block_size=bs), donate_argnums=4).lower(
        params, sds((B,), jnp.int32), sds((B,), jnp.int32), sds((B, W), jnp.int32), pool, live=sds((B,), jnp.bool_),
    ).compile()
    assert not [c for c in mosaic_calls(decode.as_text()) if "attention" in c]
    assert decode.memory_analysis().alias_size_in_bytes >= pool_bytes
