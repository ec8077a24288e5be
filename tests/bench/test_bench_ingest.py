"""CPU rehearsal of the ingest cell, and the faults its check must see."""

import dataclasses

import pytest

from bench_rehearse import no_result_line, rehearse

from repro.core.index import SpatialIndex

CELL = "porth-ingest"


def test_ingest_rehearsal_is_correct_and_prints_no_result_line(capsys):
    res = rehearse(CELL, 2**31 + 11, 2.0, "--control")
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["update_pts_per_s"]["value"] > 0
    # the control (bfloat16 brute force in the program's place, the last
    # acknowledged insert left out) fails every number
    ctl = res["control"]["checks"]
    assert not res["control"]["correct"]
    assert ctl["knn_wrong"]["value"] > 0
    assert ctl["range_wrong"]["value"] > 0
    assert ctl["live_diff"]["value"] > 0
    assert no_result_line(capsys.readouterr().out)


def _unchanged(orig):
    return lambda self, pts, mask=None: self


def _half(orig):
    return lambda self, pts, mask=None: orig(self, pts[: len(pts) // 2])


def _drop_one(orig):
    return lambda self, pts, mask=None: orig(self, pts[:-1])


@pytest.mark.parametrize("fault", [_unchanged, _half, _drop_one])
def test_ingest_check_fails_on_a_broken_insert(monkeypatch, fault):
    monkeypatch.setattr(SpatialIndex, "insert_unchecked",
                        fault(SpatialIndex.insert_unchecked))
    res = rehearse(CELL, 5, 1.0)
    assert not res["correct"]
    assert res["checks"]["live_diff"]["value"] > 0


def test_ingest_check_fails_on_points_filed_in_the_wrong_box(monkeypatch):
    """Every insert leaves each leaf's box collapsed onto its low corner:
    the points, and so the live multiset, are right; the tree is not, and
    the queries answered on the committed head show it."""
    orig = SpatialIndex.insert_unchecked

    def insert(self, pts, mask=None):
        out = orig(self, pts, mask)
        out._tree = dataclasses.replace(out._tree,
                                        bbox_hi=out._tree.bbox_lo)
        return out

    monkeypatch.setattr(SpatialIndex, "insert_unchecked", insert)
    res = rehearse(CELL, 7, 1.0)
    assert not res["correct"]
    assert res["checks"]["live_diff"]["value"] == 0
    assert (res["checks"]["knn_wrong"]["value"]
            + res["checks"]["range_wrong"]["value"]) > 0
