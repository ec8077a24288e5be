"""The benchmark's metric arithmetic on synthetic timestamps."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import stats  # noqa: E402


def test_whole_step_rate_counts_a_step_that_straddles_the_window_end():
    # window [10, 13): steps of 1.0 s, 100 points each; the third starts
    # at 12.5 and commits at 13.5, after the window's end
    steps = [(10.0, 11.0, 100), (11.0, 12.5, 100), (12.5, 13.5, 100),
             (13.5, 14.5, 100)]
    rate = stats.whole_step_rate(10.0, 3.0, steps)
    # three whole steps over 3.5 s; the fourth started after the window
    assert rate == pytest.approx(300 / 3.5)
    # counting steps inside a fixed window would read 200 / 3.0
    assert rate != pytest.approx(200 / 3.0)


def test_whole_step_rate_needs_a_step_in_the_window():
    with pytest.raises(ValueError):
        stats.whole_step_rate(0.0, 1.0, [(2.0, 3.0, 10)])


def test_latency_is_timed_from_due_time_when_the_generator_runs_late():
    # three requests due at 0.0, 0.1, 0.2; the generator stalled and
    # submitted all three at 0.5; each answer came 0.05 s after submit
    due = [0.0, 0.1, 0.2]
    submitted = [0.5, 0.5, 0.5]
    done = [s + 0.05 for s in submitted]
    lat = stats.latencies_ms(due, done)
    assert lat == pytest.approx([550.0, 450.0, 350.0])
    # timing from submission would hide the stall
    assert stats.latencies_ms(submitted, done) == pytest.approx([50.0] * 3)


def test_tail_is_over_all_requests_not_over_flush_medians():
    # 17 flushes of 10 requests at 100 ms; one flush of 30 at 1000 ms
    lat = [100.0] * 170 + [1000.0] * 30
    p95 = stats.percentile(lat, 95)
    assert p95 == pytest.approx(np.percentile(lat, 95)) == 1000.0
    # the p95 of per-flush medians weighs the slow flush as one of 18
    medians = [100.0] * 17 + [1000.0]
    assert stats.percentile(medians, 95) == pytest.approx(235.0)


@pytest.mark.parametrize("q", [0, 5, 50, 95, 100])
def test_percentile_matches_numpy_linear(q):
    x = np.random.default_rng(3).exponential(size=101)
    assert stats.percentile(list(x), q) == pytest.approx(np.percentile(x, q))


def test_spread_is_iqr_over_median():
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0, 6.0]) == pytest.approx(
        (5.25 - 1.75) / 3.5)
