"""``repro.obs``: host-sync-free tracing, counters and trace export
for the index -> engine -> server stack.

The serving contract (ROADMAP "Serving runtime", "Contracts") forbids
host reads of device values on dispatch paths — which is exactly where
naive instrumentation would put them. This subsystem is the designed
alternative: every helper below is host-side bookkeeping (monotonic
clock reads, dict updates, list appends), device values are *attached*
to spans/counters and read only at :func:`resolve` — called from
existing barriers (``SpatialServer.commit``, report time) — and the
``obs-deferred-sync`` lint rule holds the package to it.

Usage::

    from repro import obs

    rec = obs.Recorder()
    obs.install(rec)                    # or: with obs.recording(rec):
    with obs.span("serve.step", kind="insert") as sp:
        idx = idx.insert(batch)         # async dispatch
        sp.defer("rows", idx.size)      # attach, don't read
    obs.count("steps")
    obs.observe("batch_rows", 512)      # pow2-bucket histogram
    ...
    obs.resolve()                       # at a barrier: one read each
    obs.write_chrome_trace(rec, "trace.json")    # Perfetto-viewable
    # then: python -m repro.obs.view trace.json

Disabled (no recorder installed) every helper is a near-free no-op:
``span()`` returns a shared :data:`NULL_SPAN` and the counter/histogram
helpers return after one dict-slot check, so instrumentation stays in
the hot path unconditionally (overhead asserted in tests/test_obs.py).

Instrumented out of the box (counter/span names are stable API):

====================================  =================================
``engine.plan_request/_miss``         query-plan cache traffic
``engine.trace``                      query-closure (re)traces — equals
                                      ``repro.core.engine.trace_count``
``engine.route.frontier|flat``        kNN impl routing decisions
``engine.escalation_rounds``          pow2 buffer escalations per call
``engine.knn`` span                   one kNN call: plan lookup and
                                      dispatch (``shards`` attribute
                                      on a mesh)
``engine.range_count`` span           one range-count call: plan,
                                      dispatch, escalation rounds
                                      (``shards`` on a mesh)
``dist.knn.calls``                    distributed kNN launches
``dist.knn.candidates``               rows the kNN merge gathers:
                                      Q x shards x k per launch
``dist.range.calls``                  distributed range-count launches
``dist.update.routed``                rows of the all_to_all routing
                                      slab per update: shards^2 x the
                                      per-pair capacity
``engine.range_count.sync`` span      the truncation read of each round
                                      (a host wait on the device)
``engine.range_list.sync`` span       range-list's truncation and
                                      count reads of each round
``index.update_plan_miss``            update-closure compiles
``index.grow/compact/build_retry``    capacity-recovery ladder events
``serving.insert|delete`` spans       update dispatch latency
``serving.evict_block`` span          version-window backpressure stall
``serving.replay`` span               deferred-overflow replays
``serving.commit`` span               exposed commit stall, in phases:
``serving.commit.wait`` span          ``block_until_ready`` on the head
``serving.commit.check`` span         overflow/dropped and deferred
                                      point reads
``serving.commit.reclaim`` span       dropping versions, memory rebase
``serving.commit.resolve`` span       draining deferred obs reads
``batcher.flush`` span                one op group of a flush, in
                                      phases:
``batcher.pack`` span                 concatenate, pad, to the device
``batcher.call`` span                 the engine call
``batcher.split`` span                splitting out each ticket's answer
``batcher.split.fused``               groups of one-row requests split
                                      in one launch
``batcher.split.sliced``              groups sliced ticket by ticket
                                      (ragged requests, host outputs)
``batcher.coalesce_rows/pad_rows``    flush batch size / pad waste
``batcher.wait_s``                    request queue wait (submit->flush)
``batcher.flush.<reason>``            size|deadline|result|retarget|
                                      explicit
``server.mem.live_bytes``             head-version buffer bytes (gauge)
``server.mem.window_bytes``           retained-window bytes (gauge)
``server.mem.evicted_bytes``          bytes freed by window eviction
``server.shard<i>.live_points``       live points on shard i at the
                                      last commit (gauge, mesh only)
``server.shard.max_over_mean``        largest shard's live points over
                                      the mean (gauge, mesh only)
``plan.cost.<sig>.flops|bytes``       captured per-plan cost model
                                      (``Recorder(capture_costs=True)``)
``backend.mem.d<id>.bytes_in_use``    allocator stats, resolve()-only
                                      (``memory_snapshots=True``)
====================================  =================================

With ``Recorder(annotate=True)`` every span is also written into the
JAX profiler's trace (``jax.profiler.TraceAnnotation``), nested as it
ran, on the clock of the device ops: a profiler trace then says what
the host was doing in each gap between device programs.

Phase 2 adds three memory/cost/drift surfaces (ROADMAP "Observability"):
:mod:`repro.obs.memory` (``nbytes``-metadata accounting — sync-free by
construction), :mod:`repro.obs.costs` (AOT compile-cost capture at
plan-miss sites), and ``python -m repro.obs.regress`` (the perf gate
comparing a fresh smoke run against committed baselines).
"""

from __future__ import annotations

import contextlib

from . import costs
from .export import chrome_trace, write_chrome_trace
from .memory import fmt_bytes, tree_bytes
from .record import NULL_SPAN, Hist, NullSpan, Recorder, Span, pow2_bucket

__all__ = [
    "Recorder", "Span", "NullSpan", "NULL_SPAN", "Hist", "pow2_bucket",
    "install", "uninstall", "recording", "enabled", "recorder",
    "span", "count", "gauge", "observe", "defer", "defer_gauge",
    "resolve",
    "chrome_trace", "write_chrome_trace",
    "costs", "tree_bytes", "fmt_bytes",
]

# single mutable slot so the disabled-path check is one dict lookup
_STATE: dict = {"rec": None}


def install(rec: Recorder) -> Recorder:
    """Make ``rec`` the process-wide sink for the module-level helpers
    (instrumented library code records through these)."""
    _STATE["rec"] = rec
    return rec


def uninstall() -> None:
    _STATE["rec"] = None


def enabled() -> bool:
    return _STATE["rec"] is not None


def recorder() -> Recorder | None:
    """The installed recorder, or None while disabled."""
    return _STATE["rec"]


@contextlib.contextmanager
def recording(rec: Recorder | None = None):
    """Scoped install: enable obs for a block, restoring the previous
    state (including disabled) on exit. Yields the recorder."""
    rec = rec if rec is not None else Recorder()
    prev = _STATE["rec"]
    _STATE["rec"] = rec
    try:
        yield rec
    finally:
        _STATE["rec"] = prev


# -- instrumentation surface (near-free when disabled) ----------------------

def span(name: str, cat: str = "", **attrs):
    rec = _STATE["rec"]
    if rec is None:
        return NULL_SPAN
    return rec.span(name, cat, **attrs)


def count(name: str, n: float = 1) -> None:
    rec = _STATE["rec"]
    if rec is not None:
        rec.count(name, n)


def gauge(name: str, value) -> None:
    rec = _STATE["rec"]
    if rec is not None:
        rec.gauge(name, value)


def observe(name: str, value) -> None:
    rec = _STATE["rec"]
    if rec is not None:
        rec.observe(name, value)


def defer(name: str, value) -> None:
    """Attach an in-flight device scalar to counter ``name``; folded in
    at the next :func:`resolve` (no host read here)."""
    rec = _STATE["rec"]
    if rec is not None:
        rec.add_deferred(name, value)


def defer_gauge(name: str, value) -> None:
    """Attach an in-flight device scalar to gauge ``name``; set at the
    next :func:`resolve` (no host read here)."""
    rec = _STATE["rec"]
    if rec is not None:
        rec.add_deferred_gauge(name, value)


def resolve() -> int:
    """Drain deferred device reads — call from an existing barrier only
    (``commit()``, report time); returns the number resolved."""
    rec = _STATE["rec"]
    return rec.resolve() if rec is not None else 0
