"""Program spans in a profiler trace (``bench/program_spans.py``): the
four quantities they measure, idle gaps by the innermost harness or
program span, program roles from the span open at each launch, and
absences read as absent; the old fixture reads as it did."""

import importlib.util

import pytest

from bench_rehearse import ROOT

from bench import program_spans as ps
from bench import trace_reduce as tr

FIXTURES = ROOT / "tests" / "bench" / "fixtures"
OLD = FIXTURES / "tpu_serve_trace.json.gz"

MS = 1_000_000   # ns


def _ms(*xs):
    return tuple(round(x * MS) for x in xs)


def _events():
    """A 100 ms window: a kNN flush (prep op, then the kernel) and its
    split's slice program, a range flush, one ingest step (delete,
    insert, commit), with the harness's and the program's spans."""
    modules = [(0, "jit_run", *_ms(10, 30), 1),
               (0, "jit_dynamic_slice", *_ms(40, 1), 2),
               (0, "jit_run", *_ms(42, 10), 3),
               (0, "jit_run", *_ms(60, 10), 4),
               (0, "jit_run", *_ms(70, 5), 5)]
    ops = [(0, "fusion s32[8]", *_ms(10, 10), False),
           (0, "tpu_custom_call f32[8]", *_ms(20, 20), True),
           (0, "copy s32[8]", *_ms(40, 1), False),
           (0, "sort s32[8]", *_ms(42, 10), False),
           (0, "fusion s32[4]", *_ms(60, 10), False),
           (0, "fusion s32[4]", *_ms(70, 5), False)]
    launches = [(_ms(6)[0], "jit_run"), (_ms(8)[0], "jit_dynamic_slice"),
                (_ms(26)[0], "jit_run"), (_ms(55.5)[0], "jit_run"),
                (_ms(57)[0], "jit_run")]
    spans = [("bench.window", *_ms(0, 100)),
             ("bench.submit", *_ms(4, 50)),
             ("bench.dispatch", *_ms(55, 3)),
             ("bench.commit", *_ms(57.9, 30.2))]
    program = [("batcher.flush", *_ms(4, 20)),
               ("batcher.pack", *_ms(4, 0.5)),
               ("batcher.call", *_ms(4.5, 2.5)),
               ("engine.knn", *_ms(4.6, 2.2)),
               ("batcher.split", *_ms(7, 17)),
               ("batcher.flush", *_ms(24, 30)),
               ("batcher.pack", *_ms(24, 1)),
               ("batcher.call", *_ms(25, 28)),
               ("engine.range_count", *_ms(25.1, 27.8)),
               ("engine.range_count.sync", *_ms(27, 25.8)),
               ("batcher.split", *_ms(53, 1)),
               ("serving.delete", *_ms(55, 0.8)),
               ("serving.insert", *_ms(56, 2)),
               ("serving.commit", *_ms(58, 30)),
               ("serving.commit.wait", *_ms(58, 20)),
               ("serving.commit.check", *_ms(78, 2)),
               ("serving.commit.reclaim", *_ms(80, 1)),
               ("serving.commit.resolve", *_ms(81, 1))]
    return tr.Events(modules, ops, launches, spans), program


def test_the_four_quantities_on_a_known_window():
    p = ps.ProgramSpans(*_events())
    assert p.flush_split_ms() == pytest.approx((17 + 1) / 2)
    assert p.range_sync_ms() == pytest.approx(25.8)
    assert p.commit_host_ms() == pytest.approx(30 - 20)
    # the step's last update op ends at 75 ms, its wait at 78 ms
    assert p.commit_late_ms() == pytest.approx(3.0)


def _moved_wait(start, dur):
    ev, program = _events()
    return ev, [s if s[0] != "serving.commit.wait" else
                ("serving.commit.wait", *_ms(start, dur)) for s in program]


def test_a_wait_that_ends_before_the_device_reads_zero_late():
    assert ps.ProgramSpans(*_moved_wait(58, 10)).commit_late_ms() == 0.0


def test_a_wait_begun_after_the_device_finished_reads_its_own_length():
    # an open loop commits long after its update ran: no wake-up to time
    p = ps.ProgramSpans(*_moved_wait(76, 0.5))
    assert p.commit_late_ms() == pytest.approx(0.5)
    ((c, w, late),) = p.commit_steps()
    assert c[0] == "serving.commit" and w[0] == "serving.commit.wait"


def test_idle_gaps_go_to_the_innermost_harness_or_program_span():
    p = ps.ProgramSpans(*_events())
    assert dict(p.idle_gaps()) == pytest.approx({
        "engine.knn": 0.010, "engine.range_count.sync": 0.001,
        "serving.insert": 0.008, "serving.commit": 0.025})
    # by harness spans alone, as trace_reduce attributes them
    assert dict(p.red.idle_gaps()) == pytest.approx({
        "bench.submit": 0.011, "bench.dispatch": 0.008,
        "bench.commit": 0.025})


def test_roles_from_program_spans_match_the_harness():
    p = ps.ProgramSpans(*_events())
    harness = {m[1]: m[0] for m in p.red.modules}
    prog = p.roles()
    assert [(harness[s], prog[s]) for s in sorted(harness)] == [
        ("knn", "knn"), ("other", None), ("range", "range"),
        ("update", "update"), ("update", "update")]


def test_without_program_spans_every_quantity_is_absent():
    ev, _ = _events()
    p = ps.ProgramSpans(ev, [])
    for q in (p.flush_split_ms, p.range_sync_ms, p.commit_host_ms,
              p.commit_late_ms):
        assert q() is None
    assert p.idle_gaps() == p.red.idle_gaps()
    assert set(p.roles().values()) == {None}


def test_unmatched_launches_leave_late_and_roles_absent():
    ev, program = _events()
    ev.launches = ev.launches[1:]
    p = ps.ProgramSpans(ev, program)
    assert p.commit_late_ms() is None and p.roles() == {}
    assert p.commit_host_ms() == pytest.approx(10.0)   # spans alone


def test_saved_events_load_in_both_readers(tmp_path):
    ev, program = _events()
    path = str(tmp_path / "ev.json.gz")
    ps.save(ev, program, path)
    ev2, program2 = ps.load(path)
    assert program2 == program
    assert tr.load_events(path).to_json() == ev2.to_json()
    assert tr.Reduced(ev2).summary() == tr.Reduced(ev).summary()


# -- the recorded trace of PR 13, which has no program spans -----------------

def _reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_old_fixture_has_no_program_spans_and_reads_as_before():
    from types import SimpleNamespace
    ev, program = ps.load(str(OLD))
    assert program == []
    p = ps.ProgramSpans(ev, program)
    for q in (p.flush_split_ms, p.range_sync_ms, p.commit_host_ms,
              p.commit_late_ms):
        assert q() is None
    r = tr.Reduced(tr.load_events(str(OLD)))
    summary = r.summary()
    assert summary.pop("device_s_by_role") == pytest.approx({
        "knn": 0.126926634, "other": 0.000328041, "range": 0.414661771,
        "update": 0.078448673})
    assert summary.pop("programs_by_role") == {"knn": 1, "other": 402,
                                               "range": 2, "update": 2}
    assert summary == pytest.approx({"window_s": 2.811329963,
                                     "busy_s": 0.620208806,
                                     "launches_matched": True})
    assert dict(r.idle_gaps()) == pytest.approx({
        "bench.wait": 1.932631319, "bench.submit": 0.256903329,
        "bench.update": 0.001586506, "host.other": 3e-9})
    assert p.idle_gaps() == r.idle_gaps()
    run = SimpleNamespace(trace=r, loop=SimpleNamespace(
        steps=None, flush_count={"knn": 1, "range_count": 2}))
    assert _reader("knn_prep_ms.serve")(run) == pytest.approx(26.870011)
    assert _reader("knn_kernel_ms.serve")(run) == pytest.approx(100.056623)
    assert _reader("range_device_ms.serve")(run) == pytest.approx(
        207.3308855)
    assert _reader("device_idle_share.serve")(run) == pytest.approx(
        100 * (1 - 0.620208806 / 2.811329963))


# -- traces recorded on a TPU v5e with the recorder installed -----------------

def _recorded(cell):
    return ps.ProgramSpans(*ps.load(str(FIXTURES / f"tpu_{cell}_spans.json.gz")))


def test_recorded_serve_trace_reads_split_and_sync():
    """The first 5.3 s of a traced ``spach-serve`` window: five kNN and
    five range-count flushes, two updates and a commit."""
    p = _recorded("serve")
    assert p.red.matched
    assert len(p.named("batcher.flush")) == 10
    assert len(p.named("batcher.split")) == 10
    assert p.flush_split_ms() == pytest.approx(182.4420939)
    assert p.range_sync_ms() == pytest.approx(195.3038346)
    assert p.commit_host_ms() == pytest.approx(1.65753)
    # the open loop commits long after its update ran: the wait alone
    assert p.commit_late_ms() == pytest.approx(0.09119)


def test_recorded_serve_roles_from_program_spans_equal_the_harness():
    p = _recorded("serve")
    harness = {m[1]: m[0] for m in p.red.modules}
    prog = p.roles()
    pairs = [(harness[s], prog[s]) for s in harness
             if harness[s] in ("knn", "range", "update")]
    assert sorted(pairs) == [("knn", "knn")] * 5 + \
        [("range", "range")] * 5 + [("update", "update")] * 4


def test_recorded_serve_submit_idle_goes_to_program_spans():
    """Idle time under ``bench.submit`` by harness spans alone is, with
    the program's spans, nearly all the batcher's per-ticket slicing."""
    p = _recorded("serve")
    submit = dict(p.red.idle_gaps())["bench.submit"]
    gaps = dict(p.idle_gaps())
    program = sum(v for k, v in gaps.items() if k.startswith(ps.PREFIXES))
    assert submit == pytest.approx(1.248894575)
    assert gaps["batcher.split"] == pytest.approx(1.235816501)
    assert gaps["bench.wait"] == pytest.approx(2.348740687)
    assert program >= 0.8 * submit


def test_recorded_ingest_trace_reads_commit_phases():
    """The first 4.5 s of a traced ``porth-ingest`` window: five steps."""
    p = _recorded("ingest")
    assert p.red.matched
    assert len(p.commit_steps()) == 5
    assert p.commit_host_ms() == pytest.approx(1.65885)
    assert p.commit_late_ms() == pytest.approx(1.1378096)
    for q in (p.flush_split_ms, p.range_sync_ms):
        assert q() is None
    prog = p.roles()
    assert [prog[m[1]] for m in p.red.modules if m[0] == "update"] == \
        ["update"] * 10
    # the idle time under bench.commit, split into commit's phases
    assert dict(p.red.idle_gaps())["bench.commit"] == pytest.approx(
        0.021343617)
    gaps = dict(p.idle_gaps())
    assert "bench.commit" not in gaps
    assert gaps["serving.commit.check"] == pytest.approx(0.021193343)
    assert gaps["serving.commit.wait"] == pytest.approx(0.000150274)
