"""The percentile and rate arithmetic on a hand-made latency list with
a stall in it."""

import pytest

from benchmarks.harness import stats


def test_percentile_is_nearest_rank_of_all_values():
    values = list(range(1, 101))  # 1..100
    assert stats.percentile(values, 50) == 50
    assert stats.percentile(values, 95) == 95
    assert stats.percentile(values, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def _sample(t_send, t_done, good=True):
    return {"t_send": t_send, "t_done": t_done, "good": good}


def test_window_counts_completions_and_keeps_the_stall():
    t0, seconds = 100.0, 10.0
    samples = [
        # eighteen quick requests, 100 ms each
        *[_sample(100.0 + 0.1 * i, 100.1 + 0.1 * i) for i in range(18)],
        _sample(101.9, 106.9),            # one stalled for 5 s
        _sample(107.0, 107.1, good=False),  # a wrong answer, in time
        _sample(109.5, 110.5),            # completes after the close
        _sample(99.0, 99.9),              # completed before the window
    ]
    got = stats.window_metrics(samples, t0, seconds)
    assert got["completed"] == 20
    # the rate is good completions over the WHOLE window, stall included
    assert got["tiles_per_s"] == pytest.approx(19 / 10.0)
    # 20 latencies: 19 of 100 ms and the stall; rank ceil(.95*20) = 19
    assert got["tile_p50_ms"] == pytest.approx(100.0)
    assert got["tile_p95_ms"] == pytest.approx(100.0)
    # two stalls put one at the 95th percentile's rank
    samples.append(_sample(102.0, 106.0))
    got = stats.window_metrics(samples, t0, seconds)
    assert got["completed"] == 21
    assert got["tile_p95_ms"] == pytest.approx(4000.0)


def test_an_empty_window_reports_no_latency():
    got = stats.window_metrics([], 0.0, 5.0)
    assert got == {"completed": 0, "tiles_per_s": 0.0}
    assert stats.latency_deciles_ms([], 0.0, 5.0) == []


def test_latency_deciles_are_of_the_window_only():
    # latencies 1..100 ms completed inside the window, one outside it
    samples = [_sample(1.0, 1.0 + i / 1e3) for i in range(1, 101)]
    samples.append(_sample(0.0, 9.0))
    assert stats.latency_deciles_ms(samples, 0.0, 5.0) == [
        10.0, 20.0, 30.0, 40.0, 50.0, 60.0, 70.0, 80.0, 90.0]


def test_completions_per_bucket_shows_a_stall():
    samples = [_sample(0.0, t) for t in (0.5, 1.0, 4.9, 5.0, 14.9, 15.0, 16.0)]
    assert stats.completions_per_bucket(samples, 0.0, 15.0, 5.0) == [3, 1, 2]
