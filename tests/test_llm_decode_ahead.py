"""A decode step in flight: where every active row is greedy the engine
chooses its tokens inside the program and launches step N+1 before it reads
step N's. The two arms (that one, and the synchronous one a wrapped
``_sample`` forces) give the same tokens for every family; a stop token,
the one end learnt a step late, costs one discarded row and nothing a
client sees; a request admitted behind a step in flight joins the next one;
the arm is read off the requests and ``_sample``, turn by turn. Toy engines,
on the CPU.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.core.config import GLOBAL_CONFIG, Config
from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams
from ray_tpu.models import gpt2, llama
from ray_tpu.models.kimi_linear import KimiLinearConfig
from ray_tpu.models.mla_moe import MlaMoeConfig
from ray_tpu.models.nemotron_h import NemotronHConfig
from ray_tpu.util import flightrec

pytestmark = pytest.mark.timeout(600)

FAMILIES = ["gpt2", "llama", "kimi_linear", "mla_moe", "nemotron_h"]
NEVER = -1  # no token stops a request: it runs its max_tokens


def llm_config(family, **kw):
    model = {
        "gpt2": lambda: dataclasses.replace(
            gpt2.GPT2Config.tiny(vocab_size=512, max_seq=128),
            dtype=jnp.float32, attn_impl="reference"),
        "llama": lambda: dataclasses.replace(
            llama.LlamaConfig.tiny(n_layer=2, d_model=64, n_head=4, n_kv_head=2, max_seq=128),
            dtype=jnp.float32, attn_impl="reference"),
        "kimi_linear": lambda: KimiLinearConfig.tiny(max_seq=128),
        "mla_moe": lambda: MlaMoeConfig.tiny(max_seq=128),
        "nemotron_h": lambda: NemotronHConfig.tiny(max_seq=128),
    }[family]()
    return LLMConfig(**{
        "model_config": model, "max_slots": 3, "max_seq": 128,
        "prefill_buckets": (16, 32, 64), "kv_block_size": 16, "prefix_chunk": 16,
        "seed": 0, "enable_prefix_caching": False, **kw,
    })


def prompts(n, seed=1, lo=5, hi=60):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 500, size=int(rng.integers(lo, hi))).tolist() for _ in range(n)]


def synchronous(eng):
    """Force the arm on which the host sees every row's logits, the way the
    benchmark's output check does: wrap ``_sample``. Returns the undo."""
    own = eng._sample
    eng._sample = lambda logits, req: own(logits, req)

    def restore():
        eng._sample = own

    return restore


def run(eng, jobs):
    """``jobs`` are (id, prompt, sampling): all handed over at once, stepped
    until done; the tokens by id."""
    for rid, prompt, sampling in jobs:
        eng.add_request(rid, prompt, sampling)
    while eng.has_unfinished():
        eng.step()
    return {r.request_id: list(r.generated) for r in eng.pop_finished()}


_REFERENCE: dict = {}


def alone(family, prompt, sampling):
    """The request's tokens with nobody beside it, on the synchronous arm:
    one engine a family, kept for the module (what a request decodes does
    not depend on what its engine ran before: test_llm*.py hold that)."""
    if family not in _REFERENCE:
        _REFERENCE[family] = LLMEngine(llm_config(family))
        synchronous(_REFERENCE[family])
    return run(_REFERENCE[family], [("x", prompt, sampling)])["x"]


def a_stop_token(family, seed, n=10, lo=5, hi=60):
    """(prompt, its greedy answer of ``n`` tokens, k): ``answer[k]`` first
    occurs at ``k >= 1``, before the answer's end, so the request stopped by
    it yields ``answer[: k + 1]`` and a decode step, not its prefill, ends
    it. A toy model often repeats one token: the first prompt that gives
    such an answer is taken."""
    for prompt in prompts(40, seed=seed, lo=lo, hi=hi):
        answer = alone(family, prompt, SamplingParams(max_tokens=n, stop_token=NEVER))
        for k in range(1, n - 2):
            if answer[k] not in answer[:k]:
                return prompt, answer, k
    raise AssertionError("no prompt's answer changes token after its first")


@pytest.mark.parametrize("family", FAMILIES)
def test_greedy_tokens_are_the_same_on_the_two_arms_under_churn(family):
    """Seven requests through three slots, prompts of three buckets, answers
    of 3 to 12 tokens: slots and blocks are reused while others decode. The
    arm that runs ahead gives what the synchronous arm gives, token for token."""
    ps = prompts(7)
    assert {next(b for b in (16, 32, 64) if b >= len(p)) for p in ps} == {16, 32, 64}
    lengths = [12, 3, 7, 9, 4, 11, 6]
    jobs = [
        (f"r{i}", p, SamplingParams(max_tokens=n, stop_token=NEVER))
        for i, (p, n) in enumerate(zip(ps, lengths))
    ]
    ahead = LLMEngine(llm_config(family))
    got = run(ahead, jobs)
    sync = LLMEngine(llm_config(family))
    synchronous(sync)
    want = run(sync, jobs)
    assert got == want and [len(got[f"r{i}"]) for i in range(7)] == lengths
    # Every program but the first after a dry engine was launched ahead. (A
    # request admitted behind a step in flight joins the next one, so the
    # same tokens may take a program or two more than on the other arm.)
    steps = ahead.stats["decode_attn_gather_steps"]
    assert steps >= sync.stats["decode_attn_gather_steps"] >= max(lengths) - 1
    assert ahead.stats["decode_steps_ahead"] == steps - 1
    assert sync.stats["decode_steps_ahead"] == 0
    assert ahead.stats["decode_rows_discarded"] == sync.stats["decode_rows_discarded"] == 0
    assert ahead.stats["tokens_generated"] == sync.stats["tokens_generated"] == sum(lengths)
    assert ahead._inflight is None and ahead.block_mgr.used_blocks == 0


@pytest.mark.parametrize("family", FAMILIES)
def test_a_stop_token_costs_one_discarded_row_and_nothing_else(family):
    """One slot. The first request ends on a stop token in the middle of its
    answer: its next row was already launched and is thrown away (no token
    after the stop, none counted). The request that takes its slot and its
    blocks decodes what it decodes alone, although the discarded row wrote
    into those blocks (and stepped the slot's state) before its prefill."""
    a, answer, k = a_stop_token(family, seed=5)
    (b,) = prompts(1, seed=6)
    many = SamplingParams(max_tokens=10, stop_token=NEVER)
    stopping = SamplingParams(max_tokens=10, stop_token=answer[k])
    eng = LLMEngine(llm_config(family, max_slots=1))
    got = run(eng, [("a", a, stopping), ("b", b, many)])
    assert got["a"] == answer[: k + 1]
    assert eng.stats["decode_rows_discarded"] == 1
    assert eng.stats["tokens_generated"] == k + 1 + 10
    assert got["b"] == alone(family, b, many)
    # the synchronous arm learns of the stop token in time: nothing to discard
    assert alone(family, a, stopping) == got["a"]
    assert _REFERENCE[family].stats["decode_rows_discarded"] == 0


@pytest.mark.parametrize("family", FAMILIES)
def test_a_request_admitted_behind_a_step_in_flight_joins_the_next_step(family):
    """The step in flight was launched without the newcomer: the turn that
    admits it reads that step for the others, and launches the next with the
    newcomer's row fed by the host (its last token came from its prefill)."""
    GLOBAL_CONFIG.flightrec, saved = True, GLOBAL_CONFIG.flightrec
    flightrec.reset()
    try:
        a, b = prompts(2, seed=7)
        many = SamplingParams(max_tokens=8, stop_token=NEVER)
        eng = LLMEngine(llm_config(family))
        eng.add_request("a", a, many)
        eng.step()  # prefill, step 1 read, step 2 in flight
        eng.step()
        assert len(eng.requests["a"].generated) == 3
        assert [r.request_id for r in eng._inflight.rows] == ["a"]
        eng.add_request("b", b, many)
        eng.step()  # admits b behind the step in flight; reads that step: a alone
        assert len(eng.requests["a"].generated) == 4
        assert len(eng.requests["b"].generated) == 1  # its prefill's token only
        assert sorted(r.request_id for r in eng._inflight.rows) == ["a", "b"]
        eng.step()
        assert len(eng.requests["a"].generated) == 5
        assert len(eng.requests["b"].generated) == 2
        batches = [
            e["extra"]["batch"]
            for e in flightrec.snapshot()["rings"]["llm"]["events"]
            if e["phase"] == "llm.decode_step"
        ]
        assert batches == [1, 1, 1, 2]
        while eng.has_unfinished():
            eng.step()
        got = {r.request_id: list(r.generated) for r in eng.pop_finished()}
        assert got == {"a": alone(family, a, many), "b": alone(family, b, many)}
    finally:
        GLOBAL_CONFIG.flightrec = saved
        flightrec.reset()


@pytest.mark.parametrize("family", FAMILIES)
def test_the_arm_is_read_off_the_rows_and_the_sampler_turn_by_turn(family):
    """A row with a temperature, or a replaced ``_sample``, makes a turn
    synchronous: nothing is launched ahead and nothing is in flight when
    ``step()`` returns; the step that was in flight is read like any other.
    Restored to its own ``_sample`` and with greedy rows only, the engine
    runs ahead again. No configuration names the arm."""
    a, b, c = prompts(3, seed=9)
    many = SamplingParams(max_tokens=40, stop_token=NEVER)
    eng = LLMEngine(llm_config(family))
    eng.add_request("a", a, many)
    eng.step()
    assert eng.stats["decode_steps_ahead"] == 1 and eng._inflight is not None

    eng.add_request("warm", b, SamplingParams(max_tokens=3, temperature=0.8, stop_token=NEVER))
    before = eng.stats["decode_steps_ahead"]
    while "warm" in eng.requests and not eng.requests["warm"].finished:
        eng.step()
        assert eng._inflight is None
    assert eng.stats["decode_steps_ahead"] == before
    eng.pop_finished()

    eng.step()  # greedy rows only: ahead again
    assert eng.stats["decode_steps_ahead"] == before + 1 and eng._inflight is not None

    restore = synchronous(eng)
    before = eng.stats["decode_steps_ahead"]
    n = len(eng.requests["a"].generated)
    for k in range(3):  # the first of these reads the step that was in flight
        eng.step()
        assert eng._inflight is None
        assert len(eng.requests["a"].generated) == n + k + 1
    assert eng.stats["decode_steps_ahead"] == before
    restore()
    eng.step()
    assert eng.stats["decode_steps_ahead"] == before + 1 and eng._inflight is not None

    eng.add_request("c", c, many)
    while eng.has_unfinished():
        eng.step()
    got = {r.request_id: list(r.generated) for r in eng.pop_finished()}
    assert got["a"] == alone(family, a, many) and got["c"] == alone(family, c, many)
    named = [f.name for cls in (LLMConfig, Config) for f in dataclasses.fields(cls)]
    assert not [n for n in named if "ahead" in n.lower() or "sample" in n.lower()]


@pytest.mark.parametrize("family", ["gpt2", "llama", "mla_moe"])  # a recurrent state has no prefix cache
def test_a_prefix_hit_after_a_discarded_row_reads_what_the_blocks_held(family):
    """The discarded row is written past the prompt, so past every block the
    prefix pool shares: the pooled blocks hold after it what they held before
    it, and a request that hits the prefix decodes what it decodes with no cache."""
    a, answer, k = a_stop_token(family, seed=11, n=8, lo=35, hi=36)  # two blocks and three tokens
    b = a[:32] + [11, 12, 13, 14, 15]
    many = SamplingParams(max_tokens=8, stop_token=NEVER)
    eng = LLMEngine(llm_config(family, max_slots=1, enable_prefix_caching=True))
    eng.add_request("a", a, SamplingParams(max_tokens=8, stop_token=answer[k]))
    eng.step()
    (entry,) = eng._prefix_pool.values()
    part = "k" if "k" in eng.pool else "ckv"
    held = np.asarray(eng.pool[part][:, entry["blocks"]])
    while eng.has_unfinished():
        eng.step()
    assert eng.stats["decode_rows_discarded"] == 1
    assert eng.pop_finished()[0].generated == answer[: k + 1]
    assert run(eng, [("b", b, many)])["b"] == alone(family, b, many)
    assert eng.stats["prefix_hits"] == 1 and eng.stats["prefix_tokens_reused"] == 32
    np.testing.assert_array_equal(np.asarray(eng.pool[part][:, entry["blocks"]]), held)


def test_a_row_that_reaches_max_seq_ends_by_count_on_both_arms():
    """``max_seq`` is a count the host knows in time, like ``max_tokens``:
    the row's last step is the one that fills its table, no step is
    launched for it after that, and nothing is discarded."""
    ps = prompts(3, seed=3, lo=50, hi=60)
    jobs = [(f"r{i}", p, SamplingParams(max_tokens=200, stop_token=NEVER)) for i, p in enumerate(ps)]
    ahead = LLMEngine(llm_config("llama", max_seq=64, max_slots=2))
    got = run(ahead, jobs)
    sync = LLMEngine(ahead.config)
    synchronous(sync)
    assert got == run(sync, jobs)
    assert [len(got[f"r{i}"]) for i in range(3)] == [64 - len(p) for p in ps]
    assert ahead.stats["decode_rows_discarded"] == 0 and ahead.stats["decode_steps_ahead"] > 0
    assert ahead._inflight is None and ahead.block_mgr.used_blocks == 0
