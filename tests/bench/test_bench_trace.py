"""Trace reduction: busy time, device time per program and kernel, idle
gaps by harness span, and absences reported as absent."""

import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench_rehearse import ROOT

from bench import trace_reduce as tr

FIXTURE = Path(__file__).parent / "fixtures" / "tpu_serve_trace.json.gz"

MS = 1_000_000   # ns


def _events():
    """A 100 ms window: a kNN flush (prep op, then the kernel), a helper
    slice, a range flush, an update (two programs) and one program that
    runs past the window's end."""
    modules = [(0, "jit_run", 10 * MS, 30 * MS, 1),
               (0, "jit_dynamic_slice", 40 * MS, 1 * MS, 2),
               (0, "jit_run", 42 * MS, 10 * MS, 3),
               (0, "jit_run", 60 * MS, 10 * MS, 4),
               (0, "jit_run", 70 * MS, 5 * MS, 5),
               (0, "jit_other", 90 * MS, 20 * MS, 6)]
    ops = [(0, "fusion s32[8]", 10 * MS, 10 * MS, False),
           (0, "tpu_custom_call f32[8]", 20 * MS, 20 * MS, True),
           (0, "copy s32[8]", 40 * MS, 1 * MS, False),
           (0, "sort s32[8]", 42 * MS, 10 * MS, False),
           (0, "sort s32[4]", 60 * MS, 10 * MS, False),
           (0, "fusion s32[4]", 70 * MS, 5 * MS, False),
           (0, "copy s32[2]", 90 * MS, 20 * MS, False)]
    launches = [(5 * MS, "jit_run"), (6 * MS, "jit_dynamic_slice"),
                (7 * MS, "jit_run"), (55 * MS, "jit_run"),
                (56 * MS, "jit_run"), (85 * MS, "jit_other")]
    spans = [("bench.window", 0, 100 * MS), ("bench.submit", 4 * MS, 5 * MS),
             ("bench.update", 54 * MS, 4 * MS), ("bench.wait", 75 * MS,
                                                 15 * MS)]
    return tr.Events(modules, ops, launches, spans)


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    r = tr.Reduced(_events())
    assert r.window_s == pytest.approx(0.1)
    # [10, 41) + [42, 52) + [60, 75) + [90, 100) = 66 ms
    assert r.busy_s == pytest.approx(0.066)
    assert r.idle_share() == pytest.approx(0.34)


def test_programs_are_found_by_kernel_and_by_the_span_that_launched_them():
    r = tr.Reduced(_events())
    assert r.matched
    assert r.device_s("knn") == pytest.approx(0.030)
    assert r.device_s("knn", kernel=True) == pytest.approx(0.020)
    assert r.device_s("knn", kernel=False) == pytest.approx(0.010)
    assert r.device_s("range") == pytest.approx(0.010)
    assert r.device_s("update") == pytest.approx(0.015)


def test_idle_gaps_go_to_the_innermost_open_span():
    gaps = dict(tr.Reduced(_events()).idle_gaps())
    assert gaps == pytest.approx({"bench.submit": 0.010, "host.other": 0.001,
                                  "bench.update": 0.008,
                                  "bench.wait": 0.015})


def test_a_missing_name_reads_as_absent_not_zero():
    ev = _events()
    ev.ops = [o for o in ev.ops if not o[4]]       # no kernel
    ev.launches = ev.launches[1:]                  # launches unmatched
    r = tr.Reduced(ev)
    assert not r.matched
    for role in ("knn", "range", "update"):
        assert r.device_s(role) is None
    run = SimpleNamespace(trace=r, loop=SimpleNamespace(
        steps=[(0, 0, 0)], flush_count={"knn": 3, "range_count": 3}))
    for name in ("update_device_ms.ingest", "knn_kernel_ms.serve",
                 "knn_prep_ms.serve", "range_device_ms.serve"):
        assert _reader(name)(run) is None


def _reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_recorded_chip_trace_reduces_to_its_programs():
    """The first 2.8 s of a traced ``spach-serve`` window on a TPU v5e
    (407 programs: one kNN and two range-count programs, one update's
    delete and insert, and the helpers around them), in the neutral
    form."""
    r = tr.Reduced(tr.load_events(str(FIXTURE)))
    assert r.matched
    roles = [m[0] for m in r.modules]
    assert (roles.count("knn"), roles.count("range"),
            roles.count("update")) == (1, 2, 2)
    assert r.window_s == pytest.approx(2.811329963)
    assert r.busy_s == pytest.approx(0.620208806)
    assert r.device_s("knn", kernel=True) == pytest.approx(0.100056623)
    assert r.device_s("knn", kernel=False) == pytest.approx(0.026870011)
    assert r.device_s("range") == pytest.approx(0.414661771)
    assert r.device_s("update") == pytest.approx(0.078448673)
    top = dict(r.top_ops(3))
    assert top["range:sort (s32[256,500564]{0,1:T(8,128)}, "
               "s32[256,500564]{"] == pytest.approx(0.263524023)
    run = SimpleNamespace(trace=r, loop=SimpleNamespace(
        steps=None, flush_count={"knn": 1, "range_count": 2}))
    assert _reader("knn_kernel_ms.serve")(run) == pytest.approx(100.056623)
    assert _reader("range_device_ms.serve")(run) == pytest.approx(
        207.3308855)
    assert 0 < _reader("device_idle_share.serve")(run) < 100
