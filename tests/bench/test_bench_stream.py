"""The general traffic generator: each kind of data, updates, arrivals and
queries is drawn from the seed alone and has the shape its name says."""

import hashlib
import json

import numpy as np
import pytest

from bench_rehearse import ROOT

from bench import reference as ref
from bench import stream as gen

SEED = 2**31 + 5
SERVE = json.loads((ROOT / "bench/traffic/serve.json").read_text())
CFG = {"n": 16000, "dim": 2, "hi": 1 << 20}


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def test_the_uniform_sliding_window_and_poisson_schedule_are_pinned():
    """The committed cells' inputs for one seed; a change to the
    generator that moves them moves every cell's yardstick."""
    ws = gen.make(CFG, SERVE, SEED, 160)
    sch = gen.Schedule(SEED, SERVE, gen.Queries(SEED, SERVE, 2, CFG["hi"],
                                                ws.live(0)))
    sch.extend_to(10.0)
    assert _digest((ws.live(3),) + ws.step(7)
                   + (sch.t, sch.op, sch.qpts, sch.lo)) == (
        "a89870a449c6ac47c3f058238ab9177be444b3265e9103d9100c25de2e34d0b8")


@pytest.mark.parametrize("data", [gen.UNIFORM,
                                  {"kind": "varden", "step": 50,
                                   "restart_p": 0.01}])
def test_points_come_from_the_seed_alone(data):
    a = gen.points(data, 2**40 + 1, 0, 3, 1000, 2, 1 << 20)
    b = gen.points(data, 2**40 + 1, 0, 3, 1000, 2, 1 << 20)
    c = gen.points(data, 2**40 + 2, 0, 3, 1000, 2, 1 << 20)
    assert a.dtype == np.int32 and a.shape == (1000, 2)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 1 << 20


def _nn_gap(pts) -> float:
    live = ref.LiveSet(pts, 1 << 20)
    return float(np.median([live.knn_d2(p, 2)[1] for p in pts[:200]]))


def test_varden_points_are_clustered():
    kw = dict(seed=SEED, stream=0, index=0, n=20000, dim=2, hi=1 << 20)
    var = gen.varden(step=50, restart_p=0.01, **kw)
    assert _nn_gap(var) * 100 < _nn_gap(gen.uniform(**kw))


def test_moving_objects_replace_what_they_delete():
    mix = {"updates": {"kind": "moving", "disp": 2000}}
    mo = gen.make(CFG, mix, SEED, 400)
    before = mo.live(4)
    old, new = mo.step(4)
    after = mo.live(5)
    assert len(after) == CFG["n"]
    assert ref.multiset_diff(np.concatenate([after, old]),
                             np.concatenate([before, new]), CFG["hi"]) == 0
    assert (np.abs(new.astype(int) - old).max(0) <= 2000).all()
    # asked out of order, the same positions
    assert np.array_equal(mo.live(2), gen.make(CFG, mix, SEED, 400).live(2))
    assert np.array_equal(mo.live(5), after)


def test_bursty_arrivals_keep_the_rate_in_bursts():
    mix = dict(SERVE, arrivals={"kind": "bursty", "burst": 8},
               rate_per_s=200)
    q = gen.Queries(SEED, mix, 2, CFG["hi"], np.zeros((1, 2), np.int32))
    sch = gen.Schedule(SEED, mix, q)
    sch.extend_to(4.9)
    assert len(sch.t) == 1000
    assert sch.t[-1] == pytest.approx(1000 / 200)
    assert len(np.unique(sch.t)) == 1000 // 8
    assert (np.diff(sch.t).reshape(-1)[np.arange(999) % 8 != 7] == 0).all()


def test_hot_queries_fall_near_zipf_weighted_centres():
    mix = dict(SERVE, queries={"kind": "hot", "centres": 50, "zipf_s": 1.0,
                               "spread": 300})
    live0 = gen.uniform(SEED, 0, 0, 5000, 2, CFG["hi"])
    q = gen.Queries(SEED, mix, 2, CFG["hi"], live0)
    pts = q.points(3, 0, 4000)
    near = np.abs(pts[:, None, :].astype(int) - q.centres[None]).max(-1)
    assert (near.min(1) <= 300).all()
    top = np.bincount(near.argmin(1), minlength=50)
    assert top[0] == top.max() and top[0] > 4 * top[25:].mean()
    lo = q.lows(3, 1, 4000)
    assert lo.min() >= 0 and (lo + q.side - 1).max() < CFG["hi"]
