"""CPU rehearsal of the serve cell, and the faults its check must see."""

import pytest

from bench_rehearse import no_result_line, rehearse

from repro.core.index import SpatialIndex

CELL = "spach-serve"


def test_serve_rehearsal_is_correct_and_prints_no_result_line(capsys):
    res = rehearse(CELL, 2**33 + 7, 2.0, "--control")
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    for m in ("setup_s", "knn_p95_ms", "range_p95_ms"):
        assert res["metrics"][m]["value"] > 0
    # the control (bfloat16 brute force in the program's place, the
    # last acknowledged insert left out) fails every number
    ctl = res["control"]["checks"]
    assert not res["control"]["correct"]
    assert ctl["knn_wrong"]["value"] > 0
    assert ctl["range_wrong"]["value"] > 0
    assert ctl["live_diff"]["value"] > 0
    assert no_result_line(capsys.readouterr().out)


def _wrong_neighbour(orig):
    def knn(self, qpts, k, *, impl="auto"):
        d2, ids = orig(self, qpts, k, impl=impl)
        return d2, ids.at[:, -1].set(ids[:, 0])
    return "knn", knn, "knn_wrong"


def _wrong_count(orig):
    return "range_count", lambda self, lo, hi: orig(self, lo, hi) + 1, \
        "range_wrong"


def _drop_one(orig):
    return ("insert_unchecked",
            lambda self, pts, mask=None: orig(self, pts[:-1]), "live_diff")


@pytest.mark.parametrize("fault, attr", [(_wrong_neighbour, "knn"),
                                         (_wrong_count, "range_count"),
                                         (_drop_one, "insert_unchecked")])
def test_serve_check_fails_on_an_altered_answer(monkeypatch, fault, attr):
    name, fn, check = fault(getattr(SpatialIndex, attr))
    monkeypatch.setattr(SpatialIndex, name, fn)
    res = rehearse(CELL, 6, 1.0)
    assert not res["correct"]
    assert res["checks"][check]["value"] > 0
