"""A prefill's keys and values written a whole block at a time
(``paged._write_blocks``) against the row of a head at a time
(``paged._write``): the same values at the same (block, offset) homes,
compared exactly, and the programs whose rows are not whole blocks (decode,
verify) still writing by row."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import paged

BLOCK, BLOCKS, W, LAYERS = 16, 24, 8, 3

# A pool tensor's (KV heads, lanes of a row) and how a prefill's rows are made.
POOLS = {
    "mistral": (8, 128),  # [L, N, 8, 16, 128], keys or values
    "mimo_window_keys": (8, 256),  # a key of 192 in a row of 256 lanes
    "mimo_full_values": (4, 128),
    "granite_packed": (4, 128),  # [value | key], heads of 64 side by side
}


def _rows(name, rng, T, dtype):
    KH, lanes = POOLS[name]
    if name == "granite_packed":
        v, k = (rng.standard_normal((T, KH, lanes // 2)) for _ in range(2))
        rows = np.concatenate([v, k], axis=-1)
    else:
        rows = rng.standard_normal((T, KH, lanes))
        if name == "mimo_window_keys":
            rows[..., 192:] = 0.0  # zeros behind a key
    return jnp.asarray(rows, dtype)


def _table(rng, held):
    """``held`` scattered blocks, the scratch block behind them."""
    table = np.zeros(W, np.int32)
    table[:held] = rng.permutation(np.arange(1, BLOCKS))[:held]
    return jnp.asarray(table)


# (start, T, blocks the request holds): where the chunk's rows go.
CASES = {
    "first_chunk_shuffled_table": (0, 64, 8),
    "later_chunk": (32, 64, 8),
    # length ends inside the chunk's third block: the padding behind it fills
    # that block and lands on the scratch block after it
    "last_chunk_ends_inside_a_block": (48, 64, 6),
    # three of four blocks are padding and name the scratch block at once
    "padded_blocks_on_the_scratch_block": (16, 64, 2),
    # blocks 6..9 of a table of 8: the last two numbers clamp to entry 7
    "past_the_tables_end": (96, 64, 8),
}


def _by_rows(pool, l, table, start, new):
    pos = start + jnp.arange(new.shape[0], dtype=jnp.int32)
    return paged._write(pool, l, table[pos // BLOCK], pos % BLOCK, new)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("case", [*CASES, "layer_traced_in_a_scan"])
@pytest.mark.parametrize("name", list(POOLS))
def test_a_block_write_leaves_the_pool_the_row_write_leaves(name, case, dtype):
    """Bit for bit, the scratch block and the padding behind a last chunk
    included, with the start a traced operand as in the programs."""
    start, T, held = CASES.get(case, (32, 64, 8))
    KH, lanes = POOLS[name]
    rng = np.random.default_rng(sum(map(ord, name + case)))
    pool = jnp.asarray(rng.standard_normal((LAYERS, BLOCKS, KH, BLOCK, lanes)), dtype)
    table, new = _table(rng, held), _rows(name, rng, T, dtype)
    start = jnp.asarray(start, jnp.int32)
    if case == "layer_traced_in_a_scan":  # every layer written, ``l`` the scan's own index

        def scanned(write):
            def body(pool, l):
                return write(pool, l, table, start, new + l.astype(dtype)), None

            return jax.jit(lambda pool: jax.lax.scan(body, pool, jnp.arange(LAYERS, dtype=jnp.int32))[0])

        want = scanned(_by_rows)(pool)
        got = scanned(functools.partial(paged._write_blocks, block_size=BLOCK))(pool)
    else:
        want = jax.jit(_by_rows, static_argnums=1)(pool, 1, table, start, new)
        got = jax.jit(paged._write_blocks, static_argnums=(1, 5))(pool, 1, table, start, new, BLOCK)
        assert np.array_equal(np.asarray(got[0]), np.asarray(pool[0]))  # another layer: untouched
    assert got.dtype == want.dtype == dtype
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(np.asarray(got), np.asarray(pool))


def test_a_prefill_that_is_not_whole_blocks_is_refused_at_trace():
    """The engine holds every prefill program to whole blocks; the write
    asserts it on its static shape and has no arm for anything else."""
    pool = jnp.zeros((1, 4, 2, BLOCK, 8))
    with pytest.raises(AssertionError):
        paged._write_blocks(pool, 0, jnp.arange(4), 0, jnp.zeros((BLOCK + 1, 2, 8)), BLOCK)


# -- which program writes at which grain ---------------------------------------


SLOTS = 2


def _pool_scatters(jaxpr, found):
    """The window of every scatter into a pool tensor ``[L, N, KH, block,
    Dh]`` (more blocks than a state ``[L', slots + 1, ...]`` has rows) in
    ``jaxpr`` and the jaxprs it holds: ``"row"`` (one row of ``Dh`` lanes an
    update) or ``"block"`` (``KH x block x Dh`` an update)."""
    for eqn in jaxpr.eqns:
        shape = eqn.invars[0].aval.shape if eqn.primitive.name == "scatter" else ()
        if len(shape) == 5 and shape[1] > SLOTS + 1 and shape[3] == BLOCK:
            window = eqn.params["dimension_numbers"].update_window_dims
            found.append({1: "row", 3: "block"}[len(window)])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pool_scatters(sub, found)
    return found


def _programs(family):
    """``{program: the grains of its pool scatters}`` of a family's prefill
    (a bucket of 32 from a traced start), decode and, where it has one,
    verify program, by their jaxprs: nothing is compiled or run."""
    from test_llm_paged_kv import _tiny_model_of

    cfg = _tiny_model_of(family)
    mod = paged.family(cfg)
    slots, width = SLOTS, 128 // BLOCK
    pool = jax.eval_shape(lambda: paged.init_block_pool(cfg, 17, BLOCK, slots))
    params = jax.eval_shape(lambda: mod.init_params(jax.random.key(0), cfg))
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    kinds = len(paged.cache(cfg).retention)
    table = i32((width,)) if kinds == 1 else i32((kinds, width))
    tables = i32((slots, *table.shape))
    grains = {}

    def prefill(params, tokens, length, start, table, pool):
        return paged.paged_prefill(params, tokens, length, start, table, pool, cfg, block_size=BLOCK, slot=length * 0)

    def decode(params, last, positions, tables, pool):
        return paged.paged_decode(params, last, positions, tables, pool, cfg, block_size=BLOCK)

    def verify(params, tokens, positions, tables, pool):
        return paged.paged_verify(params, tokens, positions, tables, pool, cfg, block_size=BLOCK)

    grains["prefill"] = _pool_scatters(
        jax.make_jaxpr(prefill)(params, i32((1, 32)), i32(()), i32(()), table, pool).jaxpr, []
    )
    grains["decode"] = _pool_scatters(
        jax.make_jaxpr(decode)(params, i32((slots,)), i32((slots,)), tables, pool).jaxpr, []
    )
    if paged.cache(cfg).hooks:
        grains["verify"] = _pool_scatters(
            jax.make_jaxpr(verify)(params, i32((slots, 4)), i32((slots,)), tables, pool).jaxpr, []
        )
    return grains


@pytest.mark.parametrize(
    "family", ["gpt2", "llama", "nemotron_h", "afmoe", "solar_open2", "mimo_v2", "granitemoehybrid"]
)
def test_prefill_writes_by_block_and_decode_and_verify_still_by_row(family):
    """Every family with keys and values per head: its prefill program holds
    block scatters alone, its decode program (and the hook families' verify
    program) the row scatter they held before."""
    grains = _programs(family)
    assert grains["prefill"] and set(grains["prefill"]) == {"block"}, grains
    for program in set(grains) - {"prefill"}:
        assert grains[program] and set(grains[program]) == {"row"}, grains
