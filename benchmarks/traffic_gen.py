"""The one traffic generator: a mix's data file and ``--seed`` in, requests out.

Everything here is a pure function of ``(mix, seed, seconds)``: the same three
give the same requests on any host. The arrival arithmetic is a copy of the
idea in ``tools/traffic_gen.py`` (one ``random.Random`` stream keyed on every
parameter; PERF.md lists the original under Open questions), with what that
file lacks: prompt and output lengths, and prompt text.

A mix never samples lengths freely. It holds a table of prompt lengths and a
table of output lengths; a run deals the tables whole, so every seed offers
the same tokens and only their order, pairing, text and timing move.
"""

from __future__ import annotations

import dataclasses
import random

KINDS = ("open-loop", "closed-loop", "train")
_PRINTABLE = [chr(c) for c in range(0x20, 0x7F)]  # one byte, one token each


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float  # offset from the window's start; 0 for a closed loop
    prompt_tokens: int  # as the engine counts them: BOS + one per byte
    max_tokens: int
    text_seed: int  # the prompt's text is drawn from this, lazily


def _rng(mix: dict, seed: int, seconds: float, what: str) -> random.Random:
    key = (
        f"bench:{what}:{seed}:{seconds}:{mix['kind']}:"
        f"{mix.get('rate_rps')}:{mix.get('clients')}:"
        f"{tuple(mix['prompt_tokens'])}:{tuple(mix['output_tokens'])}"
    )
    return random.Random(key)


def _deal(mix: dict, rng: random.Random, deals: int) -> list:
    """``deals`` shuffles of both tables, paired position by position."""
    pairs = []
    for _ in range(deals):
        prompts = list(mix["prompt_tokens"])
        outputs = list(mix["output_tokens"])
        rng.shuffle(prompts)
        rng.shuffle(outputs)
        pairs.extend(zip(prompts, outputs))
    return pairs


def n_deals(mix: dict, seconds: float) -> int:
    table = len(mix["prompt_tokens"])
    return max(1, round(mix["rate_rps"] * seconds / table))


def open_loop(mix: dict, seed: int, seconds: float) -> list:
    """The whole schedule of an open-loop run: the tables dealt
    ``n_deals`` times, arrivals a Poisson process given its count (sorted
    uniform draws over ``requests / rate_rps`` seconds)."""
    assert mix["kind"] == "open-loop", mix["kind"]
    assert len(mix["prompt_tokens"]) == len(mix["output_tokens"])
    rng = _rng(mix, seed, seconds, "schedule")
    pairs = _deal(mix, rng, n_deals(mix, seconds))
    span = len(pairs) / mix["rate_rps"]
    due = sorted(rng.random() * span for _ in pairs)
    return [
        Request(i, due[i], p, o, rng.getrandbits(62))
        for i, (p, o) in enumerate(pairs)
    ]


def closed_loop_deck(mix: dict, seed: int, seconds: float):
    """An endless deck for a closed loop: request after request, the tables
    dealt whole each round. Clients draw from it in completion order."""
    assert mix["kind"] == "closed-loop", mix["kind"]
    assert len(mix["prompt_tokens"]) == len(mix["output_tokens"])
    rng = _rng(mix, seed, seconds, "deck")
    index = 0
    while True:
        for p, o in _deal(mix, rng, 1):
            yield Request(index, 0.0, p, o, rng.getrandbits(62))
            index += 1


def prompt_text(req: Request) -> str:
    """``prompt_tokens - 1`` printable ASCII characters (the engine's
    ByteTokenizer adds BOS): random text, so no two prompts share a prefix
    longer than chance gives."""
    rng = random.Random(req.text_seed)
    return "".join(rng.choices(_PRINTABLE, k=req.prompt_tokens - 1))


def warm_requests(mix: dict) -> list:
    """One request for each prefill bucket this mix's prompts fall into and
    no other, each answering long enough to run the decode program."""
    buckets = sorted(mix["engine"]["prefill_buckets"])
    used = sorted({min(b for b in buckets if b >= p) for p in mix["prompt_tokens"]})
    return [Request(-1 - i, 0.0, b, 8, 1000 + b) for i, b in enumerate(used)]


def train_batches(job: dict, seed: int, vocab_size: int, pool: int = 8):
    """``pool`` host batches of tokens from the seed; the loop cycles them."""
    import numpy as np

    rng = np.random.default_rng(seed)
    shape = (job["global_batch"], job["seq_len"])
    out = []
    for _ in range(pool):
        tokens = rng.integers(0, vocab_size, size=shape, dtype=np.int32)
        out.append({"tokens": tokens, "targets": np.roll(tokens, -1, axis=1)})
    return out
