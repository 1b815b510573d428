import pytest

from benchmarks import stats

# Two hand-made requests. A: due 10.0, tokens at 10.2, 10.2, 10.25, 10.35.
# B: due 11.0, sent late at 11.004, tokens at 11.5 and 11.6.
RECORDS = [
    {"due": 10.0, "sent": 10.001, "tokens": [10.2, 10.2, 10.25, 10.35], "ok": True,
     "prompt_tokens": 100, "max_tokens": 4, "usage": 4},
    {"due": 11.0, "sent": 11.004, "tokens": [11.5, 11.6], "ok": True,
     "prompt_tokens": 50, "max_tokens": 2, "usage": 2},
]


def test_percentile_interpolates_between_ranks():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile([4, 1, 3, 2], 100) == 4
    assert stats.percentile([10], 99) == 10
    assert stats.percentile(list(range(101)), 99) == 99
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_gaps_first_tokens_and_time_per_token():
    assert stats.itl_ms(RECORDS) == pytest.approx([0, 50, 100, 100])
    assert stats.ttft_ms(RECORDS) == pytest.approx([200, 500])  # from due, not sent
    assert stats.tpot_ms(RECORDS) == pytest.approx([50, 100])  # (last-first)/(n-1)
    assert stats.lateness_ms(RECORDS) == pytest.approx([1, 4])


def test_tokens_are_counted_inside_the_window_only():
    assert stats.tokens_in_window(RECORDS, 10.0, 12.0) == 6
    assert stats.tokens_in_window(RECORDS, 10.21, 11.55) == 3


def test_spread_is_the_contracts():
    import statistics

    values = [186.7, 185.9, 187.6, 186.5, 186.6, 186.0]
    q = statistics.quantiles(values, n=4)
    assert stats.spread(values) == (q[2] - q[0]) / statistics.median(values)


def test_end_to_end_readers_on_the_hand_made_record():
    from benchmarks import harness

    records = {"requests": RECORDS, "window": [10.0, 12.0], "setup_s": 3.5}
    assert harness.reader("end_to_end", "itl_p99_ms")(records) == (
        pytest.approx(stats.percentile([0, 50, 100, 100], 99)), "ms")
    assert harness.reader("end_to_end", "tpot_mean_ms")(records) == (pytest.approx(75.0), "ms")
    assert harness.reader("end_to_end", "out_tok_s")(records) == (pytest.approx(3.0), "tokens/s")
    assert harness.reader("end_to_end", "setup_s")(records) == (3.5, "s")
