"""repro.obs: recorder semantics, counter parity with the engine's
trace accounting, batcher coalesce/pad counters, deferred device-read
resolution, exporter round-trips, and the disabled-mode overhead bound.

The parity tests pin the tentpole claim: the obs counters are *the
same events* the library already counts internally (engine traces,
plan-cache misses, escalation rounds), not a parallel estimate — so a
trace-count assertion and an obs-counter assertion can never drift.
"""

from __future__ import annotations

import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import engine, make_index
from repro.obs import view
from repro.serving import LatencyRecorder, MicroBatcher, SpatialServer


@pytest.fixture(autouse=True)
def _obs_disabled():
    """Every test starts and ends with no recorder installed."""
    obs.uninstall()
    yield
    obs.uninstall()


def _pts(n, dim=2, seed=0):
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, 100.0, size=(n, dim)).astype(np.float32)


# -- recorder core ----------------------------------------------------------

def test_pow2_bucket():
    assert obs.pow2_bucket(0) == 0.0
    assert obs.pow2_bucket(-3.0) == 0.0
    assert obs.pow2_bucket(1.0) == 1.0
    assert obs.pow2_bucket(3.0) == 4.0
    assert obs.pow2_bucket(4.0) == 4.0
    assert obs.pow2_bucket(0.75) == 1.0


def test_hist_summary_exact_until_retention():
    h = obs.Hist(max_samples=4)
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4 and s["min"] == 1.0 and s["max"] == 4.0
    assert s["p50"] == 2.0 and s["p99"] == 4.0
    h.observe(100.0)                      # past retention: bucket edges
    assert h.dropped == 1
    assert h.summary()["count"] == 5
    assert h.summary()["max"] == 100.0


def test_span_timing_uses_recorder_clock():
    now = [0.0]
    rec = obs.Recorder(clock=lambda: now[0])
    with rec.span("step", cat="test", kind="unit") as sp:
        now[0] = 1.5
        sp.set(rows=7)
    (ev,) = rec.events
    assert ev["name"] == "step" and ev["cat"] == "test"
    assert ev["ts"] == 0.0 and ev["dur"] == 1.5
    assert ev["args"] == {"kind": "unit", "rows": 7}
    rec.add_span("ext", 2.0, 0.5)
    assert rec.events[-1] == {"name": "ext", "ts": 2.0, "dur": 0.5}


def test_module_helpers_route_to_installed_recorder():
    rec = obs.Recorder()
    with obs.recording(rec) as r:
        assert r is rec and obs.enabled() and obs.recorder() is rec
        obs.count("c")
        obs.count("c", 2)
        obs.gauge("g", 5)
        obs.gauge("g", 3)
        obs.observe("h", 8.0)
    assert not obs.enabled()
    assert rec.counters["c"] == 3
    assert rec.gauges["g"] == {"value": 3, "max": 5, "n": 2}
    assert rec.hist("h").count == 1


# -- deferred device reads --------------------------------------------------

def test_deferred_values_resolve_only_at_barrier():
    rec = obs.Recorder()
    with obs.recording(rec):
        total = jnp.asarray([1, 2, 3]).sum()     # in-flight device value
        with obs.span("work") as sp:
            sp.defer("total", total)
        obs.defer("points", jnp.asarray(5))
        assert rec.pending == 2
        # the span already ended; its deferred slot is a placeholder
        assert rec.events[-1]["args"]["total"] is None
        assert obs.resolve() == 2
        assert rec.pending == 0
    assert rec.events[-1]["args"]["total"] == 6.0
    assert "total_resolved_s" in rec.events[-1]["args"]
    assert rec.counters["points"] == 5.0


def test_deferred_gauges_are_set_at_the_barrier():
    rec = obs.Recorder()
    with obs.recording(rec):
        obs.defer_gauge("shard0.live", jnp.asarray([4, 6]).max())
        obs.defer_gauge("shard0.live", jnp.asarray(3))
        obs.defer("rows", jnp.asarray(2))
        assert "shard0.live" not in rec.gauges and rec.pending == 3
        assert obs.resolve() == 3
    assert rec.gauges["shard0.live"] == {"value": 3.0, "max": 6.0, "n": 2}
    assert rec.counters["rows"] == 2.0
    obs.defer_gauge("off", jnp.asarray(1))      # no recorder: nothing kept
    assert rec.pending == 0 and "off" not in rec.gauges


def test_server_commit_is_the_obs_barrier():
    pts = _pts(256)
    with obs.recording() as rec:
        srv = SpatialServer.build("porth", pts, capacity_points=1024)
        with obs.span("ingest") as sp:
            srv.insert(_pts(32, seed=1))
            sp.defer("live", jnp.asarray(288))
        assert rec.pending == 1
        srv.commit()                     # commit drains deferred reads
        assert rec.pending == 0
    names = [ev["name"] for ev in rec.events]
    assert "serving.insert" in names and "serving.commit" in names


# -- parity with the library's own accounting -------------------------------

def test_engine_trace_counter_parity():
    """obs ``engine.trace`` increments next to ``_STATS["traces"]``
    inside the jitted closures, so over any recording window the obs
    delta equals the ``engine.trace_count()`` delta exactly."""
    pts = _pts(300, seed=2)
    with obs.recording() as rec:
        idx = make_index("porth", pts)
        t0 = engine.trace_count()
        c0 = rec.counters.get("engine.trace", 0)
        q = _pts(13, seed=3)             # 13 rows: a fresh plan signature
        d2a, _ = idx.knn(q, 3)
        d2b, _ = idx.knn(q, 3)           # cached plan: no new trace
        t_delta = engine.trace_count() - t0
        o_delta = rec.counters.get("engine.trace", 0) - c0
    assert t_delta >= 1
    assert o_delta == t_delta
    assert rec.counters["engine.plan_request"] >= 2
    assert rec.counters.get("engine.plan_miss", 0) >= 1
    assert sum(v for k, v in rec.counters.items()
               if k.startswith("engine.route.")) \
        == rec.counters["engine.plan_request"]
    np.testing.assert_array_equal(np.asarray(d2a), np.asarray(d2b))


def test_escalation_counter_matches_rounds_histogram():
    """``engine.escalation`` (one per extra round) must equal the sum
    of the per-call ``engine.escalation_rounds`` observations."""
    pts = _pts(2048, seed=4)
    with obs.recording() as rec:
        idx = make_index("porth", pts)
        lo = np.zeros((4, 2), dtype=np.float32)
        hi = np.full((4, 2), 100.0, dtype=np.float32)  # whole domain
        cnt = idx.range_count(lo, hi)
        idx.range_count(lo, hi)          # converged bucket: 0 rounds
    assert int(np.asarray(cnt)[0]) == 2048
    h = rec.hist("engine.escalation_rounds")
    assert h is not None and h.count == 2
    assert rec.counters.get("engine.escalation", 0) == int(h.total)


# -- batcher counters -------------------------------------------------------

def test_batcher_coalesce_pad_and_flush_reasons():
    pts = _pts(256, seed=5)
    idx = make_index("porth", pts)
    with obs.recording() as rec:
        mb = MicroBatcher(idx, max_batch=1024, max_delay_s=10.0)
        tickets = [mb.submit_knn(_pts(1, seed=10 + i)[0], 3)
                   for i in range(5)]
        assert mb.pending == 5
        mb.flush()
        [t.result() for t in tickets]
        assert rec.counters["batcher.flush.explicit"] == 1
        (flush,) = [e for e in rec.events if e["name"] == "batcher.flush"]
        assert flush["args"]["reqs"] == 5 and flush["args"]["rows"] == 5
        assert rec.hist("batcher.coalesce_rows").samples == [5.0]
        # pow2 padding: 5 rows pad to 8, so 3 wasted rows
        assert rec.hist("batcher.pad_rows").samples == [3.0]
        assert rec.hist("batcher.wait_s").count == 5
        # result-forced flush
        t = mb.submit_knn(_pts(1, seed=20)[0], 3)
        t.result()
        assert rec.counters["batcher.flush.result"] == 1
        # size-forced flush
        mb.max_batch = 2
        mb.submit_knn(_pts(2, seed=21), 3).result()
        assert rec.counters["batcher.flush.size"] == 1


# -- LatencyRecorder on obs histograms --------------------------------------

def test_latency_recorder_is_backed_by_obs_hists():
    rec = obs.Recorder()
    lr = LatencyRecorder(recorder=rec)
    lr.record("knn", 0.004, 16, start=rec.clock())
    lr.record("knn", 0.002, 16)
    assert rec.hist("lat.knn").count == 2
    s = lr.latency_summary()["knn"]
    assert s["count"] == 2
    assert s["min_ms"] == pytest.approx(2.0)
    assert s["max_ms"] == pytest.approx(4.0)
    assert lr.count("knn") == 32
    assert rec.events[-1]["name"] == "lat.knn"   # timeline span via start=
    lr.reset()                                   # drops lat.* hists only
    assert lr.latency_summary() == {}
    assert rec.events, "reset must not erase the timeline"


def test_latency_recorder_private_when_no_recorder():
    lr = LatencyRecorder()
    with lr.timer("op"):
        pass
    assert lr.latency_summary()["op"]["count"] == 1
    assert not obs.enabled()


# -- exporters and the view CLI ---------------------------------------------

def test_exporters_roundtrip_and_view_cli(tmp_path, capsys):
    rec = obs.Recorder()
    with obs.recording(rec):
        with obs.span("a", cat="x", n=1):
            with obs.span("b"):
                pass
        obs.count("c", 2)
        obs.gauge("g", 3)
        obs.observe("h", 4.0)
    chrome = tmp_path / "trace.json"
    obs.write_chrome_trace(rec, str(chrome))

    data = json.loads(chrome.read_text())
    spans = {e["name"]: e for e in data["traceEvents"] if e["ph"] == "X"}
    assert set(spans) == {"a", "b"}
    assert spans["a"]["dur"] >= spans["b"]["dur"] >= 0   # microseconds
    assert spans["a"]["args"] == {"n": 1}
    assert spans["b"]["args"] == {"parent": "a"}
    assert data["otherData"]["counters"]["c"] == 2
    assert data["otherData"]["gauges"]["g"]["value"] == 3
    assert data["otherData"]["hists"]["h"]["count"] == 1

    assert view.main([str(chrome)]) == 0
    out = capsys.readouterr().out
    assert "a" in out and "c" in out
    assert view.main([str(tmp_path / "missing.json")]) == 1
    bad = tmp_path / "bad.json"
    bad.write_text('{"nope": 1}')
    assert view.main([str(bad)]) == 1
    lines = tmp_path / "old.jsonl"        # the retired JSONL format
    lines.write_text('{"type": "meta"}\n{"type": "span"}\n')
    assert view.main([str(lines)]) == 1
    capsys.readouterr()


# -- span parents and the profiler sink -------------------------------------

def test_spans_record_their_enclosing_span_per_thread():
    import threading

    def on_another_thread():
        with obs.span("t"):               # "outer" is not open here
            pass

    rec = obs.Recorder()
    with obs.recording(rec):
        with obs.span("outer"):
            with obs.span("mid"):
                with obs.span("inner"):
                    pass
            other = threading.Thread(target=on_another_thread)
            other.start()
            other.join(timeout=10)
            assert not other.is_alive()
            sp = obs.span("manual").begin()
            sp.end()
    parents = {e["name"]: e.get("parent") for e in rec.events}
    assert parents == {"inner": "mid", "mid": "outer", "outer": None,
                       "t": None, "manual": "outer"}


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``; logs its use."""

    log: list = []

    def __init__(self, name, **kw):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


def test_annotate_writes_each_span_into_the_profiler_trace(monkeypatch):
    import jax.profiler
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    _FakeAnnotation.log = []
    rec = obs.Recorder(annotate=True)
    with obs.recording(rec):
        with obs.span("serving.commit"):
            with obs.span("serving.commit.wait"):
                pass
            obs.count("c")                # counters write no annotation
        sp = obs.span("batcher.flush").begin()
        sp.end()
    assert _FakeAnnotation.log == [
        ("enter", "serving.commit"), ("enter", "serving.commit.wait"),
        ("exit", "serving.commit.wait"), ("exit", "serving.commit"),
        ("enter", "batcher.flush"), ("exit", "batcher.flush")]
    assert [e.get("parent") for e in rec.events] == \
        ["serving.commit", None, None]


def test_profiler_untouched_without_annotate(monkeypatch):
    import jax.profiler

    def boom(*a, **k):
        raise AssertionError("jax.profiler touched")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    assert obs.span("x") is obs.NULL_SPAN          # nothing installed
    with obs.span("x") as sp:
        assert sp is obs.NULL_SPAN
    with obs.recording(obs.Recorder()) as rec:     # installed, no sink
        with obs.span("y"):
            pass
    assert [e["name"] for e in rec.events] == ["y"]


# -- disabled mode ----------------------------------------------------------

def test_disabled_mode_is_near_free():
    assert not obs.enabled()
    assert obs.span("x") is obs.NULL_SPAN
    with obs.span("x", a=1) as sp:
        assert sp is obs.NULL_SPAN
        assert sp.set(a=2) is sp
        assert sp.defer("k", object()) is sp
        assert sp.done
    assert obs.resolve() == 0
    # each disabled helper is one dict-slot check; even a slow 1-core
    # CI box does 300k of them in well under the bound
    t0 = time.perf_counter()
    for _ in range(100_000):
        obs.count("c")
        obs.observe("h", 1.0)
        obs.gauge("g", 1)
    assert time.perf_counter() - t0 < 2.0


def test_disabled_mode_records_nothing():
    pts = _pts(128, seed=6)
    idx = make_index("porth", pts)
    idx.knn(_pts(4, seed=7), 3)          # instrumented paths, obs off
    rec = obs.Recorder()
    with obs.recording(rec):
        pass
    assert not rec.counters and not rec.events


# -- thread safety ----------------------------------------------------------

def test_concurrent_increments_are_exact():
    # the batcher's worker threads and the main thread share one
    # recorder; lost updates would silently undercount
    import threading
    rec = obs.Recorder()
    n_threads, n_iter = 8, 2_000

    def work():
        for _ in range(n_iter):
            rec.count("c")
            rec.count("weighted", 2)
            rec.observe("h", 1.0)
            rec.gauge("g", 1)

    with obs.recording(rec):
        threads = [threading.Thread(target=work)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    total = n_threads * n_iter
    assert rec.counters["c"] == total
    assert rec.counters["weighted"] == 2 * total
    assert rec.hists["h"].count == total


# -- memory accounting ------------------------------------------------------

@pytest.mark.parametrize("kind", ["porth", "spac-h", "kd"])
def test_index_nbytes_matches_leaf_sum(kind):
    import jax
    idx = make_index(kind, _pts(256, seed=11))
    expect = sum(leaf.nbytes
                 for leaf in jax.tree_util.tree_leaves(idx.tree)
                 if hasattr(leaf, "nbytes"))
    assert idx.nbytes == expect > 0
    assert obs.tree_bytes(idx.tree) == expect


def test_server_memory_accounting_tracks_versions():
    srv = SpatialServer.build("spac-h", _pts(256, seed=12),
                              capacity_points=2_048, window=2)
    base = srv.memory_report()
    assert base["live_bytes"] == srv.head_index.nbytes
    assert base["window_bytes"] == base["live_bytes"]
    assert base["evictions"] == 0

    srv.insert(_pts(64, seed=13))            # retained: v0 + v1
    two = srv.memory_report()
    assert two["retained"] == 2
    assert two["window_bytes"] == sum(two["version_bytes"].values())
    assert two["window_bytes"] > two["live_bytes"]

    srv.insert(_pts(64, seed=14))            # evicts v0 (window=2)
    three = srv.memory_report()
    assert three["retained"] == 2
    assert three["evictions"] == 1
    # eviction reclaimed exactly v0's recorded bytes and the window
    # total still equals the per-version ledger
    v0 = min(two["version_bytes"])
    assert three["evicted_bytes"] == two["version_bytes"][v0]
    assert v0 not in three["version_bytes"]
    assert three["window_bytes"] == sum(three["version_bytes"].values())
    assert three["window_bytes"] < \
        two["window_bytes"] + max(three["version_bytes"].values())
    assert three["peak_window_bytes"] >= three["window_bytes"]

    srv.commit()                             # window collapses to head
    done = srv.memory_report()
    assert done["retained"] == 1
    assert done["window_bytes"] == done["live_bytes"]
    assert done["live_bytes"] == srv.head_index.nbytes


def test_server_memory_gauges_only_when_enabled():
    pts, batch = _pts(256, seed=15), _pts(64, seed=16)
    rec = obs.Recorder()
    with obs.recording(rec):
        srv = SpatialServer.build("spac-h", pts, capacity_points=1_024,
                                  window=2)
        srv.insert(batch)
        srv.commit()
    assert rec.gauges["server.mem.live_bytes"]["value"] == \
        srv.head_index.nbytes
    assert "server.mem.window_bytes" in rec.gauges

    srv2 = SpatialServer.build("spac-h", pts, capacity_points=1_024,
                               window=2)
    srv2.insert(batch)
    srv2.commit()                            # obs off: no recorder
    assert srv2.memory_report()["live_bytes"] == srv2.head_index.nbytes
    rec2 = obs.Recorder()
    with obs.recording(rec2):
        pass
    assert "server.mem.live_bytes" not in rec2.gauges


def test_memory_snapshots_only_in_resolve():
    # CPU devices report no allocator stats — the snapshot must be a
    # silent no-op there, and only run at the resolve barrier
    rec = obs.Recorder(memory_snapshots=True)
    with obs.recording(rec):
        obs.count("x")
    rec.resolve()
    backend = [k for k in rec.gauges if k.startswith("backend.mem.")]
    import jax
    has_stats = False
    for dev in jax.local_devices():
        try:
            has_stats = bool(dev.memory_stats())
        except Exception:
            pass
    assert bool(backend) == has_stats


# -- compile-cost capture ---------------------------------------------------

def test_cost_capture_records_each_plan_once():
    pts, qpts = _pts(256, seed=17), _pts(8, seed=18)
    rec = obs.Recorder(capture_costs=True)
    with obs.recording(rec):
        idx = make_index("spac-h", pts)
        idx = idx.insert(_pts(16, seed=21))  # update closure: _run_update
        idx.knn(qpts, 3)
        idx.knn(qpts, 3)                     # same plan: no re-capture
    sigs = obs.costs.plan_costs(rec.counters)
    knn_sigs = [s for s in sigs if s.startswith("knn.")]
    assert len(knn_sigs) >= 1
    for s in knn_sigs:
        assert sigs[s]["bytes"] > 0          # HLO moves real traffic
    update_sigs = [s for s in sigs if s.startswith("update.spac-h.insert")]
    assert update_sigs                       # the insert closure
    assert rec.counters["plan.cost.captured"] == len(sigs)


def test_cost_capture_off_by_default():
    pts, qpts = _pts(256, seed=19), _pts(8, seed=20)
    rec = obs.Recorder()
    with obs.recording(rec):
        idx = make_index("spac-h", pts)
        idx.knn(qpts, 3)
    assert not [k for k in rec.counters if k.startswith("plan.cost.")]


# -- view --by-name ---------------------------------------------------------

def test_view_by_name_aggregation(tmp_path, capsys):
    rec = obs.Recorder()
    with obs.recording(rec):
        for _ in range(3):
            with obs.span("op.alpha", cat="q"):
                pass
        with obs.span("op.beta"):
            pass
    chrome = tmp_path / "t.json"
    obs.write_chrome_trace(rec, str(chrome))
    report = view.load(str(chrome))
    agg = view.by_name(report["events"])
    assert agg["op.alpha"]["count"] == 3
    assert agg["op.alpha"]["cat"] == "q"
    assert agg["op.beta"]["count"] == 1
    assert agg["op.alpha"]["total_ms"] >= agg["op.alpha"]["mean_ms"]
    assert view.main([str(chrome), "--by-name", "--top", "1"]) == 0
    out = capsys.readouterr().out
    assert "op.alpha" in out and "op.beta" not in out   # top-1
    assert view.main([str(chrome), "--by-name"]) == 0
    out = capsys.readouterr().out
    assert "op.alpha" in out and "op.beta" in out
