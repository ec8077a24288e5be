"""The ``spach-serve-4chip`` cell: a CPU rehearsal on four simulated
devices, its inputs pinned for one seed, and the readers of its two
metrics on a trace recorded on four TPU v5e chips."""

import hashlib
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from bench_rehearse import ROOT

from bench import spec
from bench import stream as gen
from bench import trace_reduce as tr

CELL = "spach-serve-4chip"
SEED = 2**31 + 5

_CHILD = r"""
import json, sys
sys.path.insert(0, "tests/bench")
from repro.configs import platform
platform.simulate_mesh(4)          # before anything starts JAX
from bench_rehearse import rehearse
res = rehearse("spach-serve-4chip", 2**33 + 11, 2.0, "--control")
print("RESULT " + json.dumps(res))
"""


def test_rehearsal_on_four_devices_is_correct_and_its_control_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _CHILD], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=900)
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, out.stdout[-3000:] + out.stderr[-3000:]
    res = json.loads(lines[-1][len("RESULT "):])
    assert res["correct"], res["checks"]
    assert {k: v["value"] for k, v in res["checks"].items()} == {
        "unanswered": 0, "knn_wrong": 0, "range_wrong": 0, "live_diff": 0}
    assert res["attempted"] > 0 and res["failed"] == 0
    # range_p95_ms spreads too widely on the chip to be compared here
    assert sorted(res["metrics"]) == ["knn_p95_ms", "setup_s"]
    for m in ("setup_s", "knn_p95_ms"):
        assert res["metrics"][m]["value"] > 0
    assert not res["control"]["correct"]
    assert res["control"]["checks"]["knn_wrong"]["value"] > 0
    assert res["control"]["checks"]["live_diff"]["value"] > 0


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def test_the_cells_inputs_are_pinned():
    """Its sliding window, arrivals and queries for one seed, with n cut
    to 16,000 points in the configuration's domain; a change to the
    generator or the mix moves them."""
    cell = spec.load(ROOT, CELL)
    cfg = dict(cell.config, n=16000)
    batch = round(cfg["n"] * cell.mix["update_share"])
    ws = gen.make(cfg, cell.mix, SEED, batch)
    sch = gen.Schedule(SEED, cell.mix, gen.Queries(
        SEED, cell.mix, cfg["dim"], cfg["hi"], ws.live(0)))
    sch.extend_to(10.0)
    assert batch == 16 and cell.chips == 4
    assert _digest((ws.live(3),) + ws.step(7)
                   + (sch.t, sch.op, sch.qpts, sch.lo)) == (
        "6149515cd2f9a4369ca20c116f971bc0277a04a43d015c2f1a5afa6240881a9d")


def _reader(name):
    return spec.load(ROOT, CELL).reader(name)


MS = 1_000_000   # ns


def _four_chip_events():
    """A 100 ms window on four chips: a kNN program on chip 0 (prep, the
    kernel, then two all-gathers), a range program ending in an
    all-reduce, an update program (its routing all-to-all, then the
    tree's pass), and chips 1-2 busy for 20 and 30 ms."""
    modules = [(0, "jit_run", 10 * MS, 27 * MS, 1),
               (0, "jit_run", 42 * MS, 10 * MS, 2),
               (0, "jit_run", 60 * MS, 12 * MS, 3)]
    ops = [(0, "fusion s32[128,40]", 10 * MS, 10 * MS, False),
           (0, "tpu_custom_call f32[128,10]", 20 * MS, 15 * MS, True),
           (0, "all-gather f32[128,40]", 35 * MS, 1 * MS, False),
           (0, "all-gather s32[128,40]", 36 * MS, 1 * MS, False),
           (0, "sort s32[128,1001064]", 42 * MS, 9 * MS, False),
           (0, "all-reduce (s32[128], pred[128])", 51 * MS, 1 * MS, False),
           (0, "all-to-all s32[4,2008,2]", 60 * MS, 2 * MS, False),
           (0, "fusion u32[1001064]", 62 * MS, 10 * MS, False),
           (1, "fusion s32[128,40]", 10 * MS, 20 * MS, False),
           (2, "fusion s32[128,40]", 10 * MS, 30 * MS, False)]
    launches = [(5 * MS, "jit_run"), (6 * MS, "jit_run"),
                (56 * MS, "jit_run")]
    spans = [("bench.window", 0, 100 * MS), ("bench.submit", 4 * MS, 3 * MS),
             ("bench.update", 55 * MS, 2 * MS)]
    return tr.Events(modules, ops, launches, spans)


def _run(ev, chips=4, knn_flushes=1, updates=1):
    return SimpleNamespace(
        trace=tr.Reduced(ev, chips=chips), cell=SimpleNamespace(chips=chips),
        loop=SimpleNamespace(flush_count={"knn": knn_flushes,
                                          "range_count": knn_flushes},
                             updates=updates))


def test_merge_reads_the_collectives_inside_the_knn_programs():
    run = _run(_four_chip_events(), knn_flushes=2)
    assert run.trace.matched
    # the two all-gathers; the range program's all-reduce and the
    # update's all-to-all are not kNN's
    assert _reader("knn_merge_ms.serve4chip")(run) == pytest.approx(1.0)


def test_update_device_time_is_per_tick_of_the_update_programs():
    run = _run(_four_chip_events(), updates=2)
    assert [m[0] for m in run.trace.modules] == ["knn", "range", "update"]
    # the update program's 12 ms, routing included, over two ticks
    assert _reader("update_device_ms.serve4chip")(run) == pytest.approx(6.0)


def test_absent_work_reads_as_absent():
    ev = _four_chip_events()
    ev.ops = [o for o in ev.ops if not o[1].startswith("all-")]
    assert _reader("knn_merge_ms.serve4chip")(_run(ev)) is None
    assert _reader("update_device_ms.serve4chip")(_run(ev, updates=0)) \
        is None
    # update programs the trace cannot tell apart (launches unmatched)
    ev.launches = ev.launches[:2]
    assert _reader("update_device_ms.serve4chip")(_run(ev)) is None
    none = SimpleNamespace(trace=None, cell=SimpleNamespace(chips=4),
                           loop=SimpleNamespace(flush_count={"knn": 1},
                                                updates=1))
    for name in ("knn_merge_ms.serve4chip", "update_device_ms.serve4chip"):
        assert _reader(name)(none) is None


FIXTURE = ROOT / "tests" / "bench" / "fixtures" / "tpu_serve4chip_trace.json.gz"


def test_recorded_four_chip_trace_reduces_to_its_programs():
    """The first 3 s of a traced ``spach-serve-4chip`` window on four TPU
    v5e chips (two kNN and two range-count flushes, one update's delete
    and insert, and the helpers around them), in the neutral form. Every
    program runs on all four chips; the readers of the one-chip cell
    read chip 0's."""
    r = tr.Reduced(tr.load_events(str(FIXTURE)), chips=4)
    assert r.matched
    roles = [m[0] for m in r.modules]
    assert (roles.count("knn"), roles.count("range"),
            roles.count("update")) == (2, 2, 2)
    assert sorted(r.busy) == [0, 1, 2, 3]
    assert r.window_s == pytest.approx(3.0)
    assert r.busy_s == pytest.approx(1.4150040245)
    run = SimpleNamespace(trace=r, cell=SimpleNamespace(chips=4),
                          loop=SimpleNamespace(flush_count={
                              "knn": 2, "range_count": 2}, updates=1))
    # the two all-gathers of each kNN program; the first also waits for
    # the slowest chip's kernel
    assert _reader("knn_merge_ms.serve4chip")(run) == pytest.approx(
        2.222584)
    # the tick's delete and insert, their all-to-all routing included
    assert _reader("update_device_ms.serve4chip")(run) == pytest.approx(
        176.076363)
    assert _reader("knn_kernel_ms.serve")(run) == pytest.approx(
        119.7875735)
    assert _reader("range_device_ms.serve")(run) == pytest.approx(
        465.976142)
