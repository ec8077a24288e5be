"""Arithmetic of the end-to-end metrics.

Kept with the benchmark, apart from the program, so that a change to the
program cannot change how its numbers are computed. Pure functions of
host timestamps; ``tests/bench/test_bench_stats.py`` pins them.
"""

from __future__ import annotations

import statistics


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) of all ``values``, interpolated
    linearly between the two closest ranks (numpy's default method)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def latencies_ms(due, done):
    """Per-request latency in ms, from the time each request was *due*
    (its scheduled arrival) to the time its result was on the host. A
    generator that runs late adds its lateness to every later request."""
    if len(due) != len(done):
        raise ValueError("due and done differ in length")
    return [1e3 * (d - a) for a, d in zip(due, done)]


def whole_step_rate(window_start: float, window_s: float, steps) -> float:
    """Points per second over whole committed steps.

    ``steps`` are ``(start, committed, points)`` in the order run. Every
    step that starts inside the window counts whole, also one that
    commits after the window's end, and the elapsed time runs from the
    window's start to the commit of the last counted step. Steps are
    never cut at the window's edge, so one step more or fewer cannot
    move the rate by a step's share of the window."""
    end = window_start + window_s
    counted = [s for s in steps if s[0] < end]
    if not counted:
        raise ValueError("no step started inside the window")
    elapsed = max(s[1] for s in counted) - window_start
    return sum(s[2] for s in counted) / elapsed


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
