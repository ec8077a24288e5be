"""Serving-runtime contract tests (ROADMAP "Serving runtime (PR 3)").

Three guarantees, each asserted bit-for-bit:

* **Snapshot isolation** — queries against version ``v`` return
  identical results while ``v+1``/``v+2``'s update closures are in
  flight on device, for every registered backend.
* **Micro-batcher determinism** — coalesced, pow2-padded answers
  bit-match the answers each request would get dispatched alone.
* **Plan-cache hit rate** — the batcher's pow2 padding keeps a ragged
  request stream inside O(log max_batch) jitted query plans
  (``repro.core.engine.trace_count``), i.e. no per-request retrace.

Plus the deferred-overflow replay (``commit()`` never loses points),
the bounded version window, and a tiny end-to-end driver run.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import BACKENDS, engine, make_index
from repro.data import points as gen
from repro.serving import MicroBatcher, SpatialServer, batcher
from repro.serving.driver import DriverCfg, run_one

PHI = 8
N, Q, K = 600, 12, 4
HI = 1 << 20

_rng = np.random.default_rng(0)
PTS = _rng.integers(0, HI, size=(N, 2)).astype(np.int32)
QS = _rng.integers(0, HI, size=(Q, 2)).astype(np.int32)
BATCH = _rng.integers(0, HI, size=(128, 2)).astype(np.int32)
BOX_LO = _rng.integers(0, HI // 2, size=(Q, 2)).astype(np.int32)
BOX_HI = BOX_LO + np.int32(HI // 3)


def _server(kind: str, **kw) -> SpatialServer:
    return SpatialServer.build(kind, jnp.asarray(PTS), phi=PHI,
                               capacity_points=2 * N, **kw)


# ---------------------------------------------------------------------------
# snapshot isolation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(BACKENDS))
def test_snapshot_isolation(kind):
    """Queries against version v are bit-identical before and while
    v+1/v+2's updates are in flight; the committed head sees them."""
    srv = _server(kind)
    snap = srv.snapshot()
    d2_a, ids_a = map(np.asarray, snap.knn(QS, K))
    cnt_a = np.asarray(snap.range_count(BOX_LO, BOX_HI))

    srv.insert(jnp.asarray(BATCH))          # v+1 in flight
    srv.delete(jnp.asarray(PTS[:100]))      # v+2 in flight
    assert srv.in_flight >= 1 and srv.head_version == snap.version + 2

    d2_b, ids_b = map(np.asarray, snap.knn(QS, K))
    np.testing.assert_array_equal(d2_a, d2_b)
    np.testing.assert_array_equal(ids_a, ids_b)
    np.testing.assert_array_equal(
        cnt_a, np.asarray(snap.range_count(BOX_LO, BOX_HI)))

    v = srv.commit()
    assert v == snap.version + 2
    head = srv.snapshot()
    assert len(head) == N + BATCH.shape[0] - 100
    assert len(snap.index) == N           # the old version is untouched


def test_snapshot_of_evicted_version_raises():
    srv = _server("spac-h", window=2)
    v0 = srv.head_version
    for i in range(4):
        srv.insert(jnp.asarray(BATCH[i * 16: (i + 1) * 16]))
    assert len(srv.versions) == 2         # bounded window
    with pytest.raises(KeyError):
        srv.snapshot(v0)
    srv.commit()
    assert srv.versions == (srv.head_version,)


def test_server_rejects_donating_index():
    idx = make_index("spac-h", jnp.asarray(PTS), phi=PHI, donate=True)
    with pytest.raises(ValueError, match="non-donating"):
        SpatialServer(idx)


# ---------------------------------------------------------------------------
# deferred overflow check: commit replays, never loses points
# ---------------------------------------------------------------------------

def test_commit_recovers_deferred_overflow():
    """Async inserts past capacity set the sticky flag; commit replays
    from the last good version through the facade's recovery ladder and
    the committed head holds the exact multiset."""
    idx = make_index("spac-h", jnp.asarray(PTS), phi=PHI)  # tight rows
    srv = SpatialServer(idx, window=3)
    rng = np.random.default_rng(3)
    total = N
    for _ in range(6):
        batch = rng.integers(0, HI, size=(600, 2)).astype(np.int32)
        srv.insert(jnp.asarray(batch))
        total += 600
    srv.commit()
    assert len(srv.head_index) == total
    assert srv.stats["recoveries"] >= 1


# ---------------------------------------------------------------------------
# micro-batcher: bit-parity with per-request dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(BACKENDS))
def test_batcher_bit_parity(kind):
    """Coalesced pow2-padded answers == per-request answers, bitwise,
    for ragged kNN and range requests on every backend."""
    idx = make_index(kind, jnp.asarray(PTS), phi=PHI)
    mb = MicroBatcher(idx, max_batch=1 << 30, max_delay_s=1e9)
    spans = [(0, 1), (1, 4), (4, 9), (9, Q)]     # ragged request sizes
    knn_t = [mb.submit_knn(QS[a:b], K) for a, b in spans]
    rng_t = [mb.submit_range_count(BOX_LO[a:b], BOX_HI[a:b])
             for a, b in spans]
    lst_t = [mb.submit_range_list(BOX_LO[a:b], BOX_HI[a:b])
             for a, b in spans]
    assert mb.pending == 3 * Q
    mb.flush()
    assert mb.pending == 0
    for (a, b), t in zip(spans, knn_t):
        d2, ids = idx.knn(QS[a:b], K)
        got_d2, got_ids = t.result()
        np.testing.assert_array_equal(np.asarray(got_d2), np.asarray(d2))
        np.testing.assert_array_equal(np.asarray(got_ids),
                                      np.asarray(ids))
    for (a, b), t in zip(spans, rng_t):
        want = idx.range_count(BOX_LO[a:b], BOX_HI[a:b])
        np.testing.assert_array_equal(np.asarray(t.result()),
                                      np.asarray(want))
    for (a, b), t in zip(spans, lst_t):
        got_ids, got_cnt = t.result()
        _, want_cnt = idx.range_list(BOX_LO[a:b], BOX_HI[a:b])
        np.testing.assert_array_equal(np.asarray(got_cnt),
                                      np.asarray(want_cnt))
        # padded width may differ between batch and solo runs; the id
        # *sets* per request must not
        got = np.asarray(got_ids)
        assert ((got >= 0).sum(-1) == np.asarray(want_cnt)).all()


class _PointsTarget:
    """Answers kNN with coordinates, (d2, points, valid): a target whose
    answer has three outputs."""

    def __init__(self, idx):
        self.idx = idx

    def knn(self, qpts, k, *, impl="auto"):
        return self.idx.knn_points(qpts, k, impl=impl)


def _assert_same_arrays(got, want):
    got = got if isinstance(got, tuple) else (got,)
    want = want if isinstance(want, tuple) else (want,)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert isinstance(g, jax.Array)
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("kind", sorted(BACKENDS))
def test_batcher_one_row_groups_split_fused(kind):
    """Groups of one-row requests are split in one launch: kNN,
    range-count and a three-output kNN answer bit-match the same
    requests dispatched alone, as device arrays of the same shapes."""
    idx = make_index(kind, jnp.asarray(PTS), phi=PHI)
    mb = MicroBatcher(idx, max_batch=1 << 30, max_delay_s=1e9)
    mb3 = MicroBatcher(_PointsTarget(idx), max_batch=1 << 30,
                       max_delay_s=1e9)
    n = Q - 1                                 # 11 rows: 5 pad rows
    with obs.recording() as rec:
        knn_t = [mb.submit_knn(QS[i], K) for i in range(n)]
        rng_t = [mb.submit_range_count(BOX_LO[i], BOX_HI[i])
                 for i in range(n)]
        pts_t = [mb3.submit_knn(QS[i], K) for i in range(n)]
        assert mb.flush() == 2 and mb3.flush() == 1
    assert rec.counters.get("batcher.split.fused") == 3
    assert "batcher.split.sliced" not in rec.counters
    for i in range(n):
        _assert_same_arrays(knn_t[i].result(), idx.knn(QS[i:i + 1], K))
        _assert_same_arrays(rng_t[i].result(),
                            idx.range_count(BOX_LO[i:i + 1],
                                            BOX_HI[i:i + 1]))
        _assert_same_arrays(pts_t[i].result(),
                            tuple(idx.knn_points(QS[i:i + 1], K)))


class _HostTarget:
    """Answers range counts as host arrays."""

    def __init__(self, idx):
        self.idx = idx

    def range_count(self, lo, hi):
        return np.asarray(self.idx.range_count(lo, hi))


@pytest.mark.parametrize("case", ["ragged", "host"])
def test_batcher_split_sliced_groups(case):
    """Groups with multi-row requests, or with host outputs, are sliced
    ticket by ticket; the answers are today's slices."""
    idx = make_index("spac-h", jnp.asarray(PTS), phi=PHI)
    spans = ([(0, 1), (1, 4), (4, 9), (9, Q)] if case == "ragged"
             else [(i, i + 1) for i in range(Q)])
    mb = MicroBatcher(idx if case == "ragged" else _HostTarget(idx),
                      max_batch=1 << 30, max_delay_s=1e9)
    with obs.recording() as rec:
        ts = [mb.submit_range_count(BOX_LO[a:b], BOX_HI[a:b])
              for a, b in spans]
        mb.flush()
    assert rec.counters.get("batcher.split.sliced") == 1
    assert "batcher.split.fused" not in rec.counters
    for (a, b), t in zip(spans, ts):
        want = idx.range_count(BOX_LO[a:b], BOX_HI[a:b])
        got = t.result()
        assert isinstance(got, jax.Array if case == "ragged" else np.ndarray)
        assert (got.shape, got.dtype) == (want.shape, want.dtype)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_batcher_admission_knobs():
    """max_batch triggers a flush on its own; max_delay_s=0 flushes on
    every submit (no coalescing-by-wait)."""
    idx = make_index("spac-h", jnp.asarray(PTS), phi=PHI)
    mb = MicroBatcher(idx, max_batch=4, max_delay_s=1e9)
    ts = [mb.submit_knn(QS[i], K) for i in range(4)]
    assert all(t.done for t in ts)        # size-triggered flush
    mb0 = MicroBatcher(idx, max_batch=1 << 30, max_delay_s=0.0)
    t = mb0.submit_knn(QS[0], K)
    assert t.done                         # delay-triggered flush
    clock = [0.0]
    mb1 = MicroBatcher(idx, max_batch=1 << 30, max_delay_s=1.0,
                       clock=lambda: clock[0])
    tk = mb1.submit_knn(QS[0], K)
    assert not tk.done and mb1.poll() == 0   # deadline not reached
    clock[0] = 2.0
    assert mb1.poll() == 1 and tk.done       # cooperative deadline


def test_batcher_target_reassign_drains_pending():
    """Reassigning target flushes queued requests against the target
    they were submitted to — results are never attributed to the wrong
    version."""
    srv = _server("spac-h")
    snap = srv.snapshot()
    mb = MicroBatcher(snap, max_batch=1 << 30, max_delay_s=1e9)
    t = mb.submit_range_count(np.zeros((1, 2), np.int32),
                              np.full((1, 2), HI - 1, np.int32))
    srv.insert(jnp.asarray(BATCH))
    srv.commit()
    mb.target = srv.snapshot()            # drains against the old snap
    assert t.done
    assert int(np.asarray(t.result())[0]) == N


def test_batcher_snapshot_provider():
    """A callable target resolves at flush time, so one flush answers
    against one consistent version even as the server advances."""
    srv = _server("spac-h")
    mb = MicroBatcher(srv.snapshot, max_batch=1 << 30, max_delay_s=1e9)
    t1 = mb.submit_range_count(np.zeros((1, 2), np.int32),
                               np.full((1, 2), HI - 1, np.int32))
    srv.insert(jnp.asarray(BATCH))
    srv.commit()
    # flush happens now: answers come from the post-commit head
    assert int(np.asarray(t1.result())[0]) == N + BATCH.shape[0]


# ---------------------------------------------------------------------------
# pow2 padding keeps ragged streams on cached query plans
# ---------------------------------------------------------------------------

def test_batcher_pow2_padding_hits_cached_plans():
    """A ragged stream of request sizes compiles one plan per pow2
    bucket (not per size), and a replay of the same stream compiles
    nothing — the trace-counter bound for the serving path."""
    idx = make_index("spac-h", jnp.asarray(PTS), phi=PHI)
    mb = MicroBatcher(idx, max_batch=1 << 30, max_delay_s=1e9)
    sizes = [1, 2, 3, 5, 7, 9, 12]
    buckets = {1 << max(s - 1, 0).bit_length() for s in sizes}

    engine._knn_closure.cache_clear()
    engine.reset_trace_count()
    for s in sizes:
        mb.submit_knn(QS[:s], K)
        mb.flush()                        # one padded call per size
    assert engine.trace_count() == len(buckets), \
        (engine.trace_count(), buckets)
    for s in sizes:                       # steady state: zero retrace
        mb.submit_knn(QS[:s], K)
        mb.flush()
    assert engine.trace_count() == len(buckets)


def test_batcher_fused_split_compiles_once_per_bucket():
    """Streams of one-row requests compile the split once per pow2
    bucket, and a replay compiles nothing."""
    idx = make_index("spac-h", jnp.asarray(PTS), phi=PHI)
    mb = MicroBatcher(idx, max_batch=1 << 30, max_delay_s=1e9)
    sizes = [1, 2, 3, 5, 7, 9, 12]
    buckets = {1 << max(s - 1, 0).bit_length() for s in sizes}

    batcher._split_rows.clear_cache()
    for _ in range(2):                    # the replay compiles nothing
        for s in sizes:
            for i in range(s):
                mb.submit_knn(QS[i], K)
            mb.flush()
        # (P, K) distances and ids split together, once per bucket
        assert batcher._split_rows._cache_size() == len(buckets)


# ---------------------------------------------------------------------------
# obs spans at the serving and engine boundaries
# ---------------------------------------------------------------------------

def _inside(child: dict, parent: dict) -> bool:
    return (parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


def test_commit_phases_nest_inside_commit():
    srv = _server("porth")
    with obs.recording() as rec:
        srv.delete(jnp.asarray(PTS[:64]))
        srv.insert(jnp.asarray(BATCH))
        srv.commit()
        srv.commit()                      # nothing in flight: same phases
    commits = [e for e in rec.events if e["name"] == "serving.commit"]
    assert len(commits) == 2
    phases = ("serving.commit.wait", "serving.commit.check",
              "serving.commit.reclaim", "serving.commit.resolve")
    for c in commits:
        kids = [e for e in rec.events if e.get("parent") == "serving.commit"
                and _inside(e, c)]
        assert [e["name"] for e in kids] == list(phases)
        assert sum(e["dur"] for e in kids) <= c["dur"]
    assert len(srv.snapshot()) == N - 64 + BATCH.shape[0]


def test_batcher_flush_covers_pack_call_and_split():
    idx = make_index("spac-h", jnp.asarray(PTS), phi=PHI)
    mb = MicroBatcher(idx, max_batch=1 << 30, max_delay_s=1e9)
    with obs.recording() as rec:
        kt = [mb.submit_knn(QS[i], K) for i in range(3)]
        rt = [mb.submit_range_count(BOX_LO[i], BOX_HI[i]) for i in range(3)]
        assert mb.flush() == 2            # one group per op
    flushes = [e for e in rec.events if e["name"] == "batcher.flush"]
    assert sorted(f["args"]["op"] for f in flushes) == ["knn",
                                                        "range_count"]
    for f in flushes:
        kids = [e for e in rec.events if e.get("parent") == "batcher.flush"
                and _inside(e, f)]
        assert [e["name"] for e in kids] == ["batcher.pack",
                                             "batcher.call",
                                             "batcher.split"]
    assert sum(e["name"] == "batcher.split" for e in rec.events) == 2
    calls = [e for e in rec.events if e["name"] in ("engine.knn",
                                                    "engine.range_count")]
    assert sorted(e["name"] for e in calls) == ["engine.knn",
                                                "engine.range_count"]
    assert all(e["parent"] == "batcher.call" for e in calls)
    assert all(t.done for t in kt + rt)


def test_range_flush_without_escalation_syncs_once():
    idx = make_index("spac-h", jnp.asarray(PTS), phi=PHI)
    mb = MicroBatcher(idx, max_batch=1 << 30, max_delay_s=1e9)
    mb.submit_range_count(BOX_LO, BOX_HI)
    mb.flush()                            # converges the row bucket
    with obs.recording() as rec:
        t = mb.submit_range_count(BOX_LO, BOX_HI)
        mb.flush()
    assert rec.hist("engine.escalation_rounds").samples == [0.0]
    (call,) = [e for e in rec.events if e["name"] == "engine.range_count"]
    syncs = [e for e in rec.events if e["name"] == "engine.range_count.sync"]
    assert len(syncs) == 1
    assert syncs[0]["parent"] == "engine.range_count"
    assert _inside(syncs[0], call)
    lo, hi = BOX_LO[:, None, :], BOX_HI[:, None, :]
    want = ((PTS[None] >= lo) & (PTS[None] <= hi)).all(-1).sum(-1)
    np.testing.assert_array_equal(np.asarray(t.result()), want)


# ---------------------------------------------------------------------------
# traces + driver
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scenario", gen.SCENARIOS)
def test_traces_deterministic(scenario):
    a = gen.make_trace(scenario, seed=4, n=300, batch=32, steps=3)
    b = gen.make_trace(scenario, seed=4, n=300, batch=32, steps=3)
    assert a.max_live == b.max_live
    np.testing.assert_array_equal(np.asarray(a.bootstrap),
                                  np.asarray(b.bootstrap))
    for sa, sb in zip(a.steps, b.steps):
        np.testing.assert_array_equal(np.asarray(sa.insert),
                                      np.asarray(sb.insert))
        np.testing.assert_array_equal(np.asarray(sa.delete),
                                      np.asarray(sb.delete))


def test_churn_deletes_land():
    """Churn steps retire a quarter of the *previous* batch — points
    that exist when the (delete-before-insert) step applies, so the
    replayed live count matches Trace.max_live bookkeeping (regression:
    deleting from the step's own not-yet-inserted batch no-op'd every
    delete)."""
    n, batch, steps = 300, 32, 3
    tr = gen.make_trace("uniform", n=n, batch=batch, steps=steps)
    idx = make_index("spac-h", tr.bootstrap, phi=PHI,
                     capacity_points=tr.max_live)
    for step in tr.steps:
        idx = idx.delete(step.delete).insert(step.insert)
    assert len(idx) == n + steps * (batch - batch // 4) == tr.max_live


def test_moving_objects_conserves_size():
    """moving-objects deletes exactly what it displaces: replaying the
    trace keeps the live count at n."""
    tr = gen.make_trace("moving-objects", n=300, batch=64, steps=3)
    assert tr.max_live == 300
    idx = make_index("spac-h", tr.bootstrap, phi=PHI)
    for step in tr.steps:
        idx = idx.delete(step.delete).insert(step.insert)
    assert len(idx) == 300


def test_driver_end_to_end_tiny():
    """run_one reports every op's percentiles and the sliding window
    holds the live set constant."""
    cfg = DriverCfg(n=400, batch=64, steps=2, warmup=1, queries=8, k=4)
    out = run_one("spac-h", "sliding-window", cfg)
    lat = out["latency_ms"]
    for op in ("insert", "delete", "knn", "range", "commit"):
        assert lat[op]["count"] > 0, op
        assert {"p50_ms", "p95_ms", "p99_ms"} <= set(lat[op]), op
    assert out["final_size"] == 400
    assert out["recoveries"] == 0
    assert out["throughput"]["query_per_s"] > 0
