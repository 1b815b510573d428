"""The flight recorder's spans on the serving path: the inside of the decode
step, a prefill span that ends where its logits reach the host, queue and
admission, the pump's gap, the hop into the replica; and the names the
jitted programs carry into a device trace. Toy engine, on the CPU.
"""

import asyncio
import dataclasses
import functools
import http.client
import json
import time

import cloudpickle
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu.core import serialization
from ray_tpu.core.config import GLOBAL_CONFIG, Config
from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams
from ray_tpu.llm.serve_llm import LLMServer
from ray_tpu.models import gpt2, llama
from ray_tpu.serve.replica import ReplicaActor
from ray_tpu.util import flightrec, trace_export

pytestmark = pytest.mark.timeout(300)

MS = 1e-3


@pytest.fixture(autouse=True)
def _recorder_on_and_empty():
    saved = GLOBAL_CONFIG.flightrec
    GLOBAL_CONFIG.flightrec = True
    flightrec.reset()
    yield
    GLOBAL_CONFIG.flightrec = saved
    flightrec.reset()


def llm_config(family="gpt2", **kw):
    tiny = {
        "gpt2": gpt2.GPT2Config.tiny(vocab_size=512, max_seq=128),
        "llama": llama.LlamaConfig.tiny(
            n_layer=2, d_model=64, n_head=4, n_kv_head=2, max_seq=128),
    }[family]
    model = dataclasses.replace(tiny, dtype=jnp.float32, attn_impl="reference")
    return LLMConfig(**{
        "model_config": model, "max_slots": 2, "max_seq": 64,
        "prefill_buckets": (16, 32), "kv_block_size": 16,
        "prefix_chunk": 16, "seed": 0, "enable_prefix_caching": False, **kw,
    })


def events(plane="llm"):
    ring = flightrec.snapshot()["rings"].get(plane, {"events": []})
    return ring["events"]


def of(phase, plane="llm"):
    return [e for e in events(plane) if e["phase"] == phase]


def end(e):
    return e["t"] + e["dur_s"]


FAMILIES = pytest.mark.parametrize("family", ["gpt2", "llama"])


@pytest.mark.parametrize("arm", ["ahead", "synchronous"])
@FAMILIES
def test_the_three_parts_of_a_decode_step_add_up_to_it(family, arm):
    """One ``llm.decode_step`` a turn, tiled by its three parts, on both
    arms. Running ahead, a turn's dispatch is the NEXT step's launch and its
    read-back the few bytes of tokens the program chose; the last turn
    launches nothing (both rows end at the step in flight by count), so its
    span starts where that step was launched, with no dispatch. A wrapped
    ``_sample`` makes every turn synchronous: launch, the logits' copy,
    sampling on the host."""
    eng = LLMEngine(llm_config(family))
    if arm == "synchronous":
        own = eng._sample
        eng._sample = lambda logits, req: own(logits, req)
    eng.generate(["hello there", "abc"], SamplingParams(max_tokens=5))
    steps = of("llm.decode_step")
    parts = [of(p) for p in
             ("llm.decode_dispatch", "llm.decode_readback", "llm.decode_sample")]
    assert len(steps) == 4 and all(len(p) == 4 for p in parts)
    for step, dispatch, readback, sample in zip(steps, *parts):
        assert dispatch["t"] == step["t"]
        assert end(dispatch) == readback["t"] and end(readback) == sample["t"]
        total = dispatch["dur_s"] + readback["dur_s"] + sample["dur_s"]
        assert abs(total - step["dur_s"]) < MS
        assert dispatch["extra"]["batch"] == sample["extra"]["batch"] == 2
        assert step["extra"]["batch"] == 2 and step["extra"]["discarded"] == 0
        assert readback["extra"]["bytes"] == {
            "ahead": 2 * 4,  # [max_slots] int32
            "synchronous": 2 * 512 * 4,  # [max_slots, vocab] f32
        }[arm]
    ahead = [s["extra"]["ahead"] for s in steps]
    assert ahead == ([1, 1, 1, 0] if arm == "ahead" else [0, 0, 0, 0])
    assert eng.stats["decode_steps_ahead"] == sum(ahead)
    for k in range(3):  # a turn that launches starts its span there, after the one before
        assert steps[k + 1]["t"] >= end(steps[k]) or (arm, k) == ("ahead", 2)
    if arm == "ahead":  # the last turn: from the launch of the step it read
        dispatches = parts[0]
        assert dispatches[3]["dur_s"] == 0.0
        assert steps[2]["t"] < steps[3]["t"] < end(steps[2])


def test_a_decode_step_says_how_much_of_its_tables_is_live():
    """``kv_blocks_live`` is the blocks the step's live rows attend, their
    own key included, and ``kv_blocks_table`` what a gather of every table
    brings back: two prompts of 15 and 30 tokens in blocks of 16, so the
    first crosses into its second block at its second step, the other into
    its third at the third; the steps are
    counted under the arm their program was built with."""
    eng = LLMEngine(llm_config("llama"))
    prompts = [[7] * 15, [9] * 30]
    for i, p in enumerate(prompts):
        eng.add_request(str(i), p, SamplingParams(max_tokens=5, temperature=0.0))
    while eng.has_unfinished():
        eng.step()
    steps = of("llm.decode_step")
    assert len(steps) == 4  # the first token of each came from its prefill
    for k, step in enumerate(steps):
        positions = [len(p) + k for p in prompts]  # where step k writes
        assert step["extra"]["kv_blocks_live"] == sum(
            -(-(pos + 1) // 16) for pos in positions
        )
        assert step["extra"]["kv_blocks_table"] == 2 * (64 // 16)
    assert [s["extra"]["kv_blocks_live"] for s in steps] == [3, 4, 5, 5]
    assert eng.stats["decode_attn_gather_steps"] == 4  # a CPU gathers
    assert eng.stats["decode_attn_kernel_steps"] == 0


def test_the_decode_arm_follows_platform_and_shapes_and_nothing_a_user_sets(monkeypatch):
    """The kernel on a TPU where the head and block sizes are whole tiles
    and no mesh spans chips, the gather otherwise; no field of LLMConfig or
    of the global configuration names it."""
    import inspect

    import jax

    from ray_tpu.models import paged
    from ray_tpu.models.kimi_linear import KimiLinearConfig
    from ray_tpu.models.mla_moe import MlaMoeConfig
    from ray_tpu.models.nemotron_h import NemotronHConfig

    tiling = llama.LlamaConfig.tiny(n_layer=1, d_model=256, n_head=2, n_kv_head=1)
    small_head = llama.LlamaConfig.tiny(n_layer=1, d_model=128, n_head=2, n_kv_head=1)
    assert (tiling.head_dim, small_head.head_dim) == (128, 64)
    two_chips = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",))
    one_chip = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tp",))
    cases = [
        (tiling, 16, None, True), (tiling, 32, one_chip, True),
        (small_head, 16, None, False),  # GPT-2's heads of 64 as well
        (tiling, 8, None, False),  # half a bf16 sublane tile
        (tiling, 16384, None, False),  # a chunk of one block past VMEM
        (tiling, 16, two_chips, False),  # a Mosaic call is not partitioned
        (KimiLinearConfig.tiny(), 16, None, False),  # programs of its own
        (MlaMoeConfig.tiny(), 16, None, False),  # likewise: it gathers its latent rows
        # programs of its own over keys and values per head: its attention
        # blocks make the same choice (at the published head of 128; tiny: 16)
        (NemotronHConfig.tiny(head_dim=128), 16, None, True),
        (NemotronHConfig.tiny(), 16, None, False),
    ]
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        for cfg, block, mesh, on_tpu in cases:
            want = on_tpu and backend == "tpu"
            assert paged.decode_attends_in_place(cfg, block, mesh=mesh) == want
    eng = LLMEngine(llm_config(model_config=tiling, max_seq=64))
    assert eng._decode_arm == "decode_attn_kernel_steps"  # "tpu" still patched
    assert paged.decode_attention(paged.attention_kind(small_head), 16, None, False) is paged._attend_gathered
    assert paged.decode_attention(paged.attention_kind(tiling), 16, two_chips, False) is paged._attend_gathered
    # Nothing to set: the decision's only inputs are the model's shapes, the
    # block size and the mesh, and no configuration names it.
    assert list(inspect.signature(paged.decode_attends_in_place).parameters) == [
        "cfg", "block_size", "mesh"]
    named = [f.name for c in (LLMConfig, Config) for f in dataclasses.fields(c)]
    assert not [n for n in named if "attn" in n.lower() or "kernel" in n.lower()]


class SlowLogits:
    """Logits whose copy to the host takes a while, as a device's would:
    ``np.asarray`` calls ``__array__``, and notes when it was done."""

    def __init__(self, logits, done: list):
        self.logits, self.done = logits, done

    @property
    def shape(self):
        return self.logits.shape

    def __array__(self, dtype=None, copy=None):
        time.sleep(0.03)
        out = np.asarray(self.logits)
        self.done.append(time.monotonic())
        return out


def slow_readback(eng, attr, called: list, done: list):
    """Wrap a jitted prefill program of the engine: note when the dispatch
    was entered and left, and hand back logits that are slow to read."""
    program = getattr(eng, attr)

    def wrapped(*args):
        t_in = time.monotonic()
        cache, logits = program(*args)
        called.append((t_in, time.monotonic()))
        return cache, SlowLogits(logits, done)

    setattr(eng, attr, wrapped)


@pytest.mark.parametrize("case", ["whole", "llama", "prefill_only", "chunked"])
def test_a_prefill_span_ends_where_its_logits_reach_the_host(case):
    kw = {"prefill_chunk_tokens": 16} if case == "chunked" else {}
    eng = LLMEngine(llm_config("llama" if case == "llama" else "gpt2", **kw))
    called, done = [], []
    slow_readback(eng, "_pg_prefill", called, done)
    prompt = list(range(3, 3 + (30 if case == "chunked" else 12)))
    t_before = time.monotonic()
    eng.add_request("r", prompt, SamplingParams(max_tokens=2),
                    prefill_only=case == "prefill_only")
    while eng.has_unfinished():
        eng.step()
    spans = of("llm.prefill_chunk" if case == "chunked" else "llm.prefill")
    assert len(spans) == len(called) == (2 if case == "chunked" else 1)
    for span, (t_in, t_out) in zip(spans, called):
        # it starts where it did: just before the dispatch is entered
        assert t_before <= span["t"] <= t_in
        assert end(span) >= t_out
        assert span["rid"] == "r" and span["extra"]["bucket"] in (16, 32)
    # only the logits that are sampled from are read; their span waits for them
    assert len(done) == 1
    last = spans[-1]
    assert end(last) >= done[0] and last["dur_s"] >= 0.03
    assert end(last) - done[0] < 5 * MS
    for early in spans[:-1]:  # chunks nobody reads end with their dispatch
        assert end(early) < done[0] - 0.03
    first_token = of("llm.first_token")[0]
    assert end(last) <= end(first_token) + MS


@FAMILIES
def test_queue_and_admit_lie_inside_the_first_token_interval(family):
    eng = LLMEngine(llm_config(family))
    for rid, prompt in (("a", "hello there"), ("b", "abc")):
        eng.add_request(rid, prompt, SamplingParams(max_tokens=2))
    while eng.has_unfinished():
        eng.step()
    for rid in ("a", "b"):
        (queue,) = [e for e in of("llm.queue") if e["rid"] == rid]
        (admit,) = [e for e in of("llm.admit") if e["rid"] == rid]
        (first,) = [e for e in of("llm.first_token") if e["rid"] == rid]
        (prefill,) = [e for e in of("llm.prefill") if e["rid"] == rid]
        assert first["t"] - MS <= queue["t"] <= end(queue) == admit["t"]
        assert end(admit) <= end(first) + MS
        assert admit["t"] <= prefill["t"] and end(prefill) <= end(admit)
        assert admit["extra"] == {
            "tokens": prefill["extra"]["tokens"], "reused": 0,
            "wave": prefill["extra"]["wave"]}
    # "b" waited for "a"'s prefill: its queue span covers a's admission
    (qb,) = [e for e in of("llm.queue") if e["rid"] == "b"]
    (aa,) = [e for e in of("llm.admit") if e["rid"] == "a"]
    assert qb["t"] <= aa["t"] and end(qb) >= end(aa)


# -- the admitting turn, and every launch numbered ------------------------------

# Llama's arm of the engine, and a family that brings its own programs.
ARMS = pytest.mark.parametrize("arm", ["ahead", "synchronous"])
WAVE_FAMILIES = pytest.mark.parametrize("family", ["llama", "kimi_linear"])


def wave_config(family, **kw):
    if family == "kimi_linear":
        from ray_tpu.models.kimi_linear import KimiLinearConfig

        kw["model_config"] = KimiLinearConfig.tiny(max_seq=128)
    return llm_config(family if family == "llama" else "gpt2", **kw)


def admitted_ids(eng):
    """Requests that have taken a slot, whether or not they still hold it."""
    return {
        r.request_id for r in eng.requests.values()
        if r.generated or r.prefilling or r.slot >= 0
    }


@functools.lru_cache(maxsize=None)
def drive(family, arm, recorder=True, **kw):
    """Five requests through two slots, turn by turn: four handed over at
    once and a fifth while both slots are held. Of every turn: what stood
    at its entry (counted here, from the engine's books), the waves it
    recorded and the requests it admitted. One run a case, kept."""
    GLOBAL_CONFIG.flightrec = recorder
    eng = LLMEngine(wave_config(family, **kw))
    if arm == "synchronous":
        own = eng._sample
        eng._sample = lambda logits, req: own(logits, req)
    work = [("a", 12, 3), ("b", 20, 6), ("c", 5, 4), ("d", 30, 2), ("e", 9, 3)]
    for rid, n, out in work[:4]:
        eng.add_request(rid, list(range(3, 3 + n)), SamplingParams(max_tokens=out))
    turns = []
    while eng.has_unfinished():
        if len(turns) == 2:
            rid, n, out = work[4]
            eng.add_request(rid, list(range(3, 3 + n)), SamplingParams(max_tokens=out))
        turn = {
            "stalled": sum(r is not None and not r.prefilling for r in eng._slot_req),
            "waiting": sum(r.slot < 0 and not r.finished for r in eng.requests.values()),
            "before": dict(eng.stats), "admitted": admitted_ids(eng),
            "waves": len(of("llm.admit_wave")),
        }
        eng.step()
        turn["waves"] = of("llm.admit_wave")[turn["waves"]:]
        turn["admitted"] = admitted_ids(eng) - turn["admitted"]
        turn["after"] = dict(eng.stats)
        turns.append(turn)
    tokens = {r.request_id: list(r.generated) for r in eng.requests.values()}
    return dict(eng.stats), turns, events(), tokens


@ARMS
@WAVE_FAMILIES
def test_one_admit_wave_a_turn_that_admits_and_none_otherwise(family, arm):
    """``llm.admit_wave`` is recorded by the turns that launched a prefill
    or gave a request a slot, once, and by no other; its fields are what
    stood at the turn's entry and what the turn did; the spans of the
    admissions inside it carry its ordinal."""
    stats, turns, evs, _ = drive(family, arm)
    admitting = [t for t in turns if t["waves"]]
    assert [len(t["waves"]) for t in turns] == [
        int(bool(t["admitted"]) or t["after"]["prefill_tokens"] > t["before"]["prefill_tokens"])
        for t in turns
    ]
    assert 3 <= len(admitting) < len(turns) and stats["admit_waves"] == len(admitting)
    for k, turn in enumerate(admitting):
        (wave,) = turn["waves"]
        x = wave["extra"]
        assert x["wave"] == k + 1
        assert x["rows_stalled"] == turn["stalled"]
        assert x["waiting"] == turn["waiting"]
        assert x["admitted"] == len(turn["admitted"]) == x["prefills"]
        assert x["left"] == x["waiting"] - x["admitted"]
        assert x["reused"] == 0
        inside = [e for e in evs if e["phase"] in ("llm.prefill", "llm.admit")
                  and e["extra"]["wave"] == x["wave"]]
        assert sorted(e["rid"] for e in inside) == sorted(2 * list(turn["admitted"]))
        for e in inside:
            assert wave["t"] <= e["t"] and end(e) <= end(wave)
        # the prefill queued behind a decode step in flight, or behind none
        running_ahead = arm == "ahead" and turn["stalled"] > 0
        assert ("inflight_age_ms" in x) == running_ahead
        if running_ahead:
            assert 0.0 < x["inflight_age_ms"] < 60e3
    first = admitting[0]["waves"][0]["extra"]
    assert (first["waiting"], first["left"], first["admitted"], first["rows_stalled"]) == (4, 2, 2, 0)
    # the fifth request waited a turn or more without a slot: no wave for that
    assert any(t["waiting"] and not t["waves"] for t in turns)


@ARMS
@WAVE_FAMILIES
def test_the_waves_add_up_to_the_engines_counters(family, arm):
    stats, turns, evs, _ = drive(family, arm)
    waves = [w["extra"] for t in turns for w in t["waves"]]
    assert sum(w["admitted"] for w in waves) == 5
    assert sum(w["prefills"] for w in waves) == 5
    assert sum(w["tokens"] for w in waves) == stats["prefill_tokens"] == 12 + 20 + 5 + 30 + 9
    assert sum(w["padded"] for w in waves) == stats["prefill_tokens_padded"] == 16 + 32 + 16 + 32 + 16
    assert sum(w["reused"] for w in waves) == stats["prefix_tokens_reused"] == 0
    fills = [e["extra"] for e in evs if e["phase"] == "llm.prefill"]
    assert sum(f["bucket"] for f in fills) == stats["prefill_tokens_padded"]


@ARMS
@WAVE_FAMILIES
def test_seq_rises_by_one_a_launch_and_ends_at_programs_launched(family, arm):
    """Prefills and decode steps share one count, in the order they were
    launched: a prefill span carries its program's number, a decode span
    that of the step it read and of the step it launched ahead."""
    stats, _turns, evs, _ = drive(family, arm)
    launches, seen = [], set()
    for e in evs:  # in the order recorded: a turn's prefills, then its step
        x = e["extra"] if "extra" in e else {}
        if e["phase"] == "llm.prefill":
            launches.append((e["t"], x["seq"]))
        elif e["phase"] == "llm.decode_step":
            for n in (x["seq"], x["next_seq"]):
                if n and n not in seen:  # launched in this turn, read in this or the next
                    launches.append((e["t"], n))
                    seen.add(n)
            assert x["ahead"] == int(x["next_seq"] > 0)
            assert x["next_seq"] in (0, x["seq"] + 1) or arm == "ahead"
    assert [n for _t, n in launches] == list(range(1, stats["programs_launched"] + 1))
    assert [t for t, _n in launches] == sorted(t for t, _n in launches)
    steps = [e["extra"] for e in evs if e["phase"] == "llm.decode_step"]
    if arm == "ahead":  # the step launched ahead is the step the next turn reads
        for this, after in zip(steps, steps[1:]):
            assert not this["next_seq"] or after["seq"] == this["next_seq"]
    assert stats["programs_launched"] == 5 + stats["decode_attn_gather_steps"]


@WAVE_FAMILIES
def test_a_wave_holds_the_chunk_it_advanced(family):
    """With ``prefill_chunk_tokens`` set a prompt's slot is taken in one
    wave and each of its chunks launched in a later one, under the numbers
    of that wave and of its launch."""
    stats, turns, evs, _ = drive(family, "ahead", prefill_chunk_tokens=16)
    waves = [w for t in turns for w in t["waves"]]
    chunks = [e for e in evs if e["phase"] == "llm.prefill_chunk"]
    assert len(chunks) == stats["prefill_chunks"] == 4  # "b" and "d": 16 + 4, 16 + 14
    for chunk in chunks:
        (wave,) = [w for w in waves if w["extra"]["wave"] == chunk["extra"]["wave"]]
        assert wave["t"] <= chunk["t"] and end(chunk) <= end(wave)
        # one chunk a turn, and the chunk's slot was taken in an earlier wave
        assert wave["extra"]["prefills"] - wave["extra"]["admitted"] in (0, 1)
        (slot_taken,) = [e for e in evs if e["phase"] == "llm.admit" and e["rid"] == chunk["rid"]]
        assert slot_taken["extra"]["wave"] < chunk["extra"]["wave"]
    by_wave = {w["extra"]["wave"]: w["extra"] for w in waves}
    fills = [e for e in evs if e["phase"] in ("llm.prefill", "llm.prefill_chunk")]
    for n, x in by_wave.items():
        mine = [f["extra"] for f in fills if f["extra"]["wave"] == n]
        assert x["prefills"] == len(mine)
        assert x["tokens"] == sum(f["tokens"] for f in mine)
        assert x["padded"] == sum(f["bucket"] for f in mine)
    assert sum(x["tokens"] for x in by_wave.values()) == stats["prefill_tokens"] == 76
    assert sum(x["admitted"] for x in by_wave.values()) == 5
    seqs = sorted(f["extra"]["seq"] for f in fills)
    assert len(set(seqs)) == len(seqs) == 3 + 4 and seqs[-1] <= stats["programs_launched"]


@ARMS
@WAVE_FAMILIES
def test_with_the_recorder_off_the_tokens_are_the_same_and_nothing_is_recorded(family, arm):
    on_stats, _turns, _evs, on_tokens = drive(family, arm)
    flightrec.reset()
    off_stats, turns, evs, off_tokens = drive(family, arm, recorder=False)
    assert off_tokens == on_tokens and len(off_tokens) == 5
    assert evs == [] and not any(t["waves"] for t in turns)
    assert flightrec.snapshot()["rings"] == {}
    for counter in ("admit_waves", "prefill_tokens_padded", "programs_launched"):
        assert off_stats[counter] == on_stats[counter] > 0


def test_queue_starts_at_the_hand_over_a_caller_names():
    eng = LLMEngine(llm_config())
    t_handed = time.monotonic() - 0.25
    eng.add_request("r", "abc", SamplingParams(max_tokens=1), t_queued=t_handed)
    eng.step()
    (queue,) = of("llm.queue")
    assert queue["t"] == t_handed and 0.25 <= queue["dur_s"] < 0.25 + 50 * MS


def test_pump_gap_lies_between_two_steps_and_not_after_a_dry_engine():
    server = LLMServer(llm_config())

    async def stream(n):
        return [p async for p in server._stream_tokens("hello", SamplingParams(max_tokens=n))]

    async def scenario():
        await stream(4)
        await server._pump_task  # the pump has run dry and returned
        first = len(of("llm.decode_step")), len(of("llm.pump_gap"))
        await stream(3)
        await server._pump_task
        return first

    steps1, gaps1 = asyncio.run(scenario())
    steps = of("llm.decode_step")
    gaps = of("llm.pump_gap")
    pushes = of("llm.push_tokens")
    # one stream: the admitting step decodes too, so n tokens take n - 1 steps
    assert (steps1, len(steps)) == (3, 5)
    # a gap before every step but the first of each stream: none is recorded
    # across the time the engine had run dry
    assert (gaps1, len(gaps)) == (2, 3)
    assert len(pushes) == 5 and pushes[0]["extra"]["streams"] == 1
    dry = steps[3]["t"] - end(steps[2])
    assert not [g for g in gaps if g["t"] < steps[3]["t"] and end(g) > end(steps[2])]
    for gap in gaps:  # from one step's return to the next one's entry
        before = max((s for s in steps if end(s) <= gap["t"]), key=end)
        after = min((s for s in steps if end(s) >= end(gap)), key=end)
        assert steps.index(after) == steps.index(before) + 1
        assert gap["extra"]["pending"] == 0
        inside = [p for p in pushes if gap["t"] <= p["t"] and end(p) <= end(gap)]
        assert len(inside) == 1
    (queue,) = [e for e in of("llm.queue") if e["rid"] == "req-1"]
    assert queue["dur_s"] < dry
    # llm.step is a whole turn in the executor thread; with the gaps it
    # tiles a busy engine's time without a seam
    turns = of("llm.step")
    assert [t["extra"]["admitted"] for t in turns] == [1, 0, 0, 1, 0]
    assert sum(t["extra"]["finished"] for t in turns) == 2
    for gap in gaps:
        assert any(end(t) == gap["t"] for t in turns)
        assert any(t["t"] == end(gap) for t in turns)
    for step in steps:  # a step ends in its turn, and starts there if the turn launched one
        (turn,) = [t for t in turns if t["t"] <= end(step) <= end(t)]
        assert turn["t"] <= step["t"] or not step["extra"]["ahead"]
    # a stream's last turn launches nothing: its span starts in the turn before
    assert [s["extra"]["ahead"] for s in steps] == [1, 1, 0, 1, 0]


def test_idle_spans_the_dry_spell_and_nothing_while_the_engine_is_busy():
    """``llm.idle`` runs from the return of the last step of a pump that ran
    dry to the start of the next pump: with ``llm.step`` and ``llm.pump_gap``
    it tiles the engine's life. None before the first request, none between
    the turns of a stream, none after the last pump (nothing has closed it)."""
    server = LLMServer(llm_config())

    async def stream(n):
        return [p async for p in server._stream_tokens("hello", SamplingParams(max_tokens=n))]

    async def scenario():
        await stream(4)
        await server._pump_task  # ran dry
        busy = len(of("llm.idle"))
        await asyncio.sleep(0.05)
        await stream(3)
        await server._pump_task
        return busy

    assert asyncio.run(scenario()) == 0
    (idle,) = of("llm.idle")
    turns, gaps = of("llm.step"), of("llm.pump_gap")
    assert len(turns) == 5
    assert idle["t"] == end(turns[2]) and idle["dur_s"] >= 0.05
    assert end(idle) <= turns[3]["t"] < end(idle) + 50 * MS  # the new pump's hop into the executor
    for gap in gaps:  # a gap and the dry spell never overlap
        assert end(gap) <= idle["t"] or gap["t"] >= end(idle)
    assert server._t_ran_dry is not None  # the second dry spell is open still


# -- the hop into the replica -------------------------------------------------


def streaming_echo(request):
    for i in range(3):
        yield {"i": i, "x": request["x"]}


def replica_of(fn):
    return ReplicaActor(
        "d", cloudpickle.dumps(fn), serialization.dumps(((), {}))[0], None
    )


def drain(agen):
    async def run():
        return [item async for item in agen]

    return asyncio.run(run())


def test_the_replica_records_hop_in_and_first_chunk_under_the_routers_id():
    replica = replica_of(streaming_echo)
    payload = serialization.dumps((({"x": 7},), {}))[0]
    t_ingress = time.time() - 0.2
    out = drain(replica.handle_streaming("__call__", payload, "", "fr-1-0", t_ingress))
    assert [o["i"] for o in out] == [0, 1, 2]
    (hop,) = of("serve.hop_in", "serve")
    (first,) = of("serve.replica_first_chunk", "serve")
    (whole,) = of("serve.replica_exec", "serve")
    assert hop["rid"] == first["rid"] == whole["rid"] == "fr-1-0"
    assert 0.2 <= hop["dur_s"] < 0.2 + 50 * MS
    assert abs(end(hop) - first["t"]) < 5 * MS  # the entry of the handler
    assert whole["t"] == first["t"] and first["dur_s"] <= whole["dur_s"]

    async def plain():
        return await replica.handle("__call__", payload, "", "fr-1-1", time.time())

    assert len(asyncio.run(plain())) == 3  # a generator, drained to a list
    assert [e["rid"] for e in of("serve.hop_in", "serve")] == ["fr-1-0", "fr-1-1"]


@pytest.mark.parametrize("recorder", ["off", "no_id"])
def test_the_wire_call_without_an_id_records_nothing(recorder):
    """With the recorder off the router sends (method, payload, model_id)
    and nothing more; a replica called so records nothing, whether its own
    recorder is on or not."""
    GLOBAL_CONFIG.flightrec = recorder != "off"
    replica = replica_of(streaming_echo)
    payload = serialization.dumps((({"x": 7},), {}))[0]
    out = drain(replica.handle_streaming("__call__", payload, ""))
    assert [o["x"] for o in out] == [7, 7, 7]
    assert flightrec.snapshot()["rings"] == {}


@pytest.fixture(scope="module")
def cluster():
    runtime = ray_tpu.init(num_cpus=8)
    yield runtime
    from ray_tpu.serve import api as serve

    serve.shutdown()
    ray_tpu.shutdown()


def test_a_streamed_request_through_the_proxy_records_the_hop(cluster):
    from ray_tpu.serve import api as serve

    @serve.deployment(num_replicas=1)
    class Words:
        async def __call__(self, request):
            async def words():
                for w in request["body"]["text"].split():
                    await asyncio.sleep(0.01)
                    yield {"word": w}

            return words()

    serve.run(Words.bind())
    conn = http.client.HTTPConnection("127.0.0.1", serve.proxy_port(), timeout=60)
    conn.request(
        "POST", "/Words", body=json.dumps({"text": "a b c"}),
        headers={"Content-Type": "application/json", "Accept": "text/event-stream"},
    )
    body = conn.getresponse().read().decode()
    conn.close()
    assert body.count('"word"') == 3 and "[DONE]" in body

    deadline = time.time() + 30
    while True:
        by_phase: dict = {}
        for snap in trace_export.collect_snapshots(cluster=True):
            for e in snap["rings"].get("serve", {"events": []})["events"]:
                by_phase.setdefault(e["phase"], []).append((snap["pid"], e))
        if "serve.replica_first_chunk" in by_phase or time.time() > deadline:
            break
        time.sleep(0.2)
    (r_pid, hop), = by_phase["serve.hop_in"]
    (f_pid, first), = by_phase["serve.replica_first_chunk"]
    (p_pid, routed), = by_phase["serve.first_chunk"]  # the router's, in the proxy
    assert r_pid == f_pid != p_pid
    assert hop["rid"] == first["rid"] == routed["rid"]
    assert hop["rid"].startswith(f"fr-{p_pid}-")
    assert 0 < hop["dur_s"] < 5.0 and 0.01 <= first["dur_s"] < 5.0


# -- names on the device, and the ring ----------------------------------------


def _module_name(jitted, *args):
    return jitted.lower(*args).as_text().split("module @", 1)[1].split(" ", 1)[0]


@pytest.fixture(scope="module")
def engine_of():
    built = {}

    def get(family):
        if family in built:
            return built[family]
        if family == "kimi_linear":
            from ray_tpu.models.kimi_linear import KimiLinearConfig

            config = llm_config(model_config=KimiLinearConfig.tiny(max_seq=128))
        elif family == "mla_moe":
            from ray_tpu.models.mla_moe import MlaMoeConfig

            config = llm_config(model_config=MlaMoeConfig.tiny(max_seq=128))
        elif family == "nemotron_h":
            from ray_tpu.models.nemotron_h import NemotronHConfig

            config = llm_config(model_config=NemotronHConfig.tiny(max_seq=128))
        else:
            config = llm_config(family)
        return built.setdefault(family, LLMEngine(config))

    return get


@pytest.mark.parametrize("program", ["paged_prefill", "paged_decode"])
@pytest.mark.parametrize("family", ["gpt2", "llama", "kimi_linear", "mla_moe", "nemotron_h"])
def test_a_jitted_program_carries_its_name_into_the_trace(engine_of, family, program):
    """The profiler's ``XLA Modules`` line names a run after the module, and
    the module after the jitted function: ``jit_paged_decode`` for every
    family, whatever operands its program takes, where a
    ``functools.partial`` gave ``jit__unknown``. The benchmark's trace
    readers find the programs by these names."""
    eng = engine_of(family)
    toks = np.zeros((1, 16), np.int32)
    slots, width = eng.block_tables.shape
    if program == "paged_decode":  # one layout a program for every family: prev, meta
        operands = (jnp.zeros(slots, jnp.int32), np.zeros((slots, 4 + width), np.int32))
    else:  # every small operand in one int32 array
        operands = (toks, np.zeros(3 + width, np.int32))
    jitted = {"paged_prefill": eng._pg_prefill, "paged_decode": eng._pg_decode}[program]
    assert _module_name(jitted, eng.params, *operands, eng.pool) == f"jit_{program}"


def test_the_default_ring_holds_a_benchmark_window():
    assert Config().flightrec_ring_size == 16384
    saved = GLOBAL_CONFIG.flightrec_ring_size
    GLOBAL_CONFIG.flightrec_ring_size = Config().flightrec_ring_size
    try:
        for i in range(16384):
            flightrec.record("llm", "llm.decode_step", dur_s=0.0, batch=i)
        assert flightrec.drops("llm") == 0
        flightrec.record("llm", "llm.decode_step", dur_s=0.0, batch=-1)
        assert flightrec.drops("llm") == 1
    finally:
        GLOBAL_CONFIG.flightrec_ring_size = saved
