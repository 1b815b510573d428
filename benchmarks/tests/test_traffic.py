import pytest

from benchmarks import harness, traffic_gen

CODE = harness.load_json(harness.HERE, "traffic", "code-poisson.json")
BATCH = harness.load_json(harness.HERE, "traffic", "batch-backlog.json")


def test_schedule_is_a_pure_function_of_the_seed():
    a = traffic_gen.open_loop(CODE, 3000000123, 45)
    b = traffic_gen.open_loop(CODE, 3000000123, 45)
    c = traffic_gen.open_loop(CODE, 3000000124, 45)
    assert a == b
    assert [traffic_gen.prompt_text(r) for r in a[:3]] == [traffic_gen.prompt_text(r) for r in b[:3]]
    assert [r.due_s for r in a] != [r.due_s for r in c]
    assert [r.prompt_tokens for r in a] != [r.prompt_tokens for r in c]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 3000000999])
def test_every_seed_offers_the_same_work(seed):
    sched = traffic_gen.open_loop(CODE, seed, 45)
    assert len(sched) == 112
    assert sum(r.prompt_tokens for r in sched) == 71904
    assert sum(r.max_tokens for r in sched) == 5236
    assert sorted(r.prompt_tokens for r in sched) == sorted(CODE["prompt_tokens"] * 7)
    assert sorted(r.max_tokens for r in sched) == sorted(CODE["output_tokens"] * 7)
    due = [r.due_s for r in sched]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 112 / 2.5


def test_window_length_scales_the_deals():
    assert len(traffic_gen.open_loop(CODE, 1, 13)) == 32
    assert len(traffic_gen.open_loop(CODE, 1, 3)) == 16  # never less than one deal


def test_prompt_text_is_one_token_per_byte_and_unshared():
    sched = traffic_gen.open_loop(CODE, 5, 45)
    texts = [traffic_gen.prompt_text(r) for r in sched]
    for r, text in zip(sched, texts):
        assert len(text.encode()) == r.prompt_tokens - 1  # the tokenizer adds BOS
    assert len({t[:32] for t in texts}) == len(texts)


def test_closed_loop_deck_deals_whole_tables():
    deck = traffic_gen.closed_loop_deck(BATCH, 9, 45)
    first = [next(deck) for _ in range(32)]
    again = traffic_gen.closed_loop_deck(BATCH, 9, 45)
    assert first == [next(again) for _ in range(32)]
    for deal in (first[:16], first[16:]):
        assert sorted(r.prompt_tokens for r in deal) == sorted(BATCH["prompt_tokens"])
        assert sorted(r.max_tokens for r in deal) == sorted(BATCH["output_tokens"])
    assert [r.index for r in first] == list(range(32))


def test_medians_are_what_the_mix_says():
    import statistics

    assert statistics.median(CODE["prompt_tokens"]) == 512
    assert statistics.median(CODE["output_tokens"]) == 32
    assert statistics.median(BATCH["prompt_tokens"]) == 304
    assert statistics.median(BATCH["output_tokens"]) == 188


def test_warm_up_covers_the_buckets_the_mix_uses_and_no_others():
    assert [r.prompt_tokens for r in traffic_gen.warm_requests(CODE)] == [128, 256, 512, 1024, 2048]
    assert [r.prompt_tokens for r in traffic_gen.warm_requests(BATCH)] == [128, 256, 512]


def test_train_batches_come_from_the_seed():
    job = harness.load_json(harness.HERE, "traffic", "pretrain-s1024.json")
    a = traffic_gen.train_batches(job, 3000000001, 50304, pool=2)
    b = traffic_gen.train_batches(job, 3000000001, 50304, pool=2)
    assert a[0]["tokens"].shape == (8, 1024)
    assert (a[1]["tokens"] == b[1]["tokens"]).all()
    assert (a[0]["targets"][:, :-1] == a[0]["tokens"][:, 1:]).all()
    assert not (a[0]["tokens"] == a[1]["tokens"]).all()
