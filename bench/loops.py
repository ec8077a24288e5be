"""The two loops that drive the served index, and their checks.

A traffic mix's file names its loop (``"loop": "closed" | "open"``) and
sets its parameters; the points, updates, arrivals and queries come from
the general generator (``bench/stream.py``) by the kinds the
configuration's and the mix's files name. Nothing here knows a cell by
name.

* ``Ingest`` (closed loop, one client, no queries in the window): each
  step applies the stream's next update (deletes, then inserts) and
  commits, then the next step starts. The rate counts whole committed
  steps only (``stats.whole_step_rate``).
* ``Serve`` (open loop): requests arrive on a fixed schedule
  (``stream.Schedule``) whether or not the server keeps up, and go one
  by one into the ``MicroBatcher``; every ``update_every_s`` the update
  dispatched at the previous tick is committed and the next one is
  dispatched. Queries read the newest committed snapshot. Each request
  is timed from its due time to its result on the host.

The program is entered only through ``SpatialServer`` (build, insert,
delete, commit, snapshot), ``MicroBatcher`` (submit, poll, flush,
tickets) and, after the window, the committed head's
``extract_points``.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time

import numpy as np

from . import reference as ref
from . import stats
from . import stream as gen
from .stream import OPS, Queries, Schedule

clock = time.perf_counter
F32_EXACT = 1 << 24      # squared distances below it are exact in f32
_WARM = 4                # stream id of warm-up payloads
_CHECK = 5               # stream id of the ingest check's queries


def build_server(cfg: dict, points: np.ndarray, capacity: int):
    from repro.serving import SpatialServer
    return SpatialServer.build(cfg["index"], points, phi=cfg["phi"],
                               capacity_points=capacity,
                               window=cfg["version_window"],
                               **cfg.get("params", {}))


class Annotations:
    """Harness spans, written into the profiler's trace when tracing
    (``jax.profiler.TraceAnnotation``), and nothing otherwise."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


def _block(x):
    import jax
    return jax.block_until_ready(x)


class Ingest:
    """Closed-loop ingest; see the module docstring."""

    def __init__(self, cfg, mix, seed, ann, knn_impl="auto"):
        self.cfg, self.mix, self.seed, self.ann = cfg, mix, seed, ann
        self.knn_impl = knn_impl
        self.batch = round(cfg["n"] * mix["update_share"])
        self.stream = gen.make(cfg, mix, seed, self.batch)
        live0 = self.stream.live(0)
        self.queries = Queries(seed, mix, cfg["dim"], cfg["hi"], live0)
        self.srv = build_server(cfg, live0, cfg["n"] + self.batch)
        self.u = 0
        for _ in range(mix["warm_steps"]):
            self.step()
        self.steps = []

    def step(self):
        """One whole step; returns (start, dispatched, committed)."""
        dele, ins = self.stream.step(self.u)
        with self.ann("bench.dispatch"):
            t0 = clock()
            self.srv.delete(dele)
            self.srv.insert(ins)
            t1 = clock()
        with self.ann("bench.commit"):
            self.srv.commit()
            t2 = clock()
        self.u += 1
        return t0, t1, t2

    def window(self, seconds: float):
        self.seconds = seconds
        rec0 = self.srv.stats["recoveries"]
        with self.ann("bench.window"):
            self.t0 = clock()
            while clock() - self.t0 < seconds:
                self.steps.append(self.step())
        self.recoveries = self.srv.stats["recoveries"] - rec0

    # -- results --------------------------------------------------------

    def step_records(self):
        """(start, committed, points) per whole step of the window."""
        return [(s, c, 2 * self.batch) for s, _, c in self.steps]

    def attempted(self) -> int:
        return 2 * self.batch * len(self.steps)

    def failed(self) -> int:
        return 0

    def info(self) -> dict:
        return {"steps": len(self.steps), "batch": self.batch,
                "recoveries_in_window": self.recoveries,
                "step_s": [round(c - s, 6) for s, _, c in self.steps]}

    def check(self, control: bool = False) -> dict:
        """What the window's updates made, read back from the committed
        head: its live multiset against the stream's window after ``u``
        steps, and a sample drawn from the seed of kNN and range-count
        queries answered on it through the ``MicroBatcher`` against the
        brute force over that window. A point filed in the wrong leaf or
        box leaves the multiset right and the answers wrong. The control
        puts the bfloat16 brute force in the program's place and leaves
        out the last acknowledged insert."""
        from repro.serving import MicroBatcher
        cfg, mix, k = self.cfg, self.mix, self.mix["k"]
        hi, n, side = cfg["hi"], mix["check_sample"], self.queries.side
        qpts = self.queries.points(_CHECK, 0, n)
        lo = self.queries.lows(_CHECK, 1, n)
        want = self.stream.live(self.u)
        lv = ref.LiveSet(want, hi)
        if control:
            got = want[:-self.batch]
            knn = [knn_ok_lowp(lv, q, k) for q in qpts]
            cnt = [ref.range_count_lowp(lv.pts, a, a + side - 1)
                   for a in lo]
        else:
            snap = self.srv.snapshot()
            batcher = MicroBatcher(snap, max_batch=4 * n, max_delay_s=1e9)
            kt = [batcher.submit_knn(q, k, impl=self.knn_impl)
                  for q in qpts]
            rt = [batcher.submit_range_count(a, a + side - 1) for a in lo]
            batcher.flush()
            pts, ok = snap.index.extract_points()
            tree = np.asarray(pts), np.asarray(ok)
            got = tree[0][tree[1]]
            knn = [knn_ok(lv, tree, q, k, t.result())
                   for q, t in zip(qpts, kt)]
            cnt = [int(np.asarray(t.result()).reshape(-1)[0]) for t in rt]
        return {"knn_wrong": knn.count(False),
                "range_wrong": sum(c != lv.range_count(a, a + side - 1)
                                   for c, a in zip(cnt, lo)),
                "live_diff": ref.multiset_diff(got, want, hi)}


def knn_ok(lv, tree, q, k: int, answer) -> bool:
    """One kNN answer on the version whose live set is ``lv`` and whose
    flattened (points, valid) are ``tree``: k valid ids of live points,
    whose exact distances are the brute force's k smallest, and whose
    returned f32 distances are exact where f32 can be."""
    want = lv.knn_d2(q, k)
    d2, ids = (np.asarray(x)[0] for x in answer)
    flat, ok = tree
    if not ((ids >= 0).all() and ok[ids].all()):
        return False
    pts = flat[ids]
    exact = ref.sq_dist(pts, q)
    if not np.array_equal(np.sort(exact), want):
        return False
    if not lv.contains(pts).all():
        return False
    small = exact < F32_EXACT
    return np.array_equal(d2[small].astype(np.int64), exact[small])


def knn_ok_lowp(lv, q, k: int) -> bool:
    """The control's kNN answer, judged as :func:`knn_ok` judges the
    program's distances."""
    pts = ref.knn_points_lowp(lv.pts, q, k)
    return np.array_equal(np.sort(ref.sq_dist(pts, q)), lv.knn_d2(q, k))


class Serve:
    """Open-loop serving with background sliding-window updates; see the
    module docstring."""

    def __init__(self, cfg, mix, seed, ann, knn_impl="auto"):
        from repro.serving import MicroBatcher
        self.cfg, self.mix, self.seed, self.ann = cfg, mix, seed, ann
        self.knn_impl = knn_impl
        self.k = mix["k"]
        self.batch = round(cfg["n"] * mix["update_share"])
        self.stream = gen.make(cfg, mix, seed, self.batch)
        live0 = self.stream.live(0)
        self.queries = Queries(seed, mix, cfg["dim"], cfg["hi"], live0)
        self.srv = build_server(cfg, live0, cfg["n"] + self.batch)
        self.u = 0
        self.dispatched = False
        self.retain, self.kept = set(), {}
        self._dispatch_update()
        self._commit_update()
        self.batcher = MicroBatcher(lambda: self.snap,
                                    max_batch=mix["max_batch"],
                                    max_delay_s=mix["max_delay_ms"] / 1e3)
        self._warm_queries()

    # -- updates --------------------------------------------------------

    def _dispatch_update(self):
        dele, ins = self.stream.step(self.u)
        self.srv.delete(dele)
        self.srv.insert(ins)
        self.u += 1
        self.dispatched = True

    def _commit_update(self):
        self.srv.commit()
        self.snap = self.srv.snapshot()
        self.snap_u = self.u
        self.dispatched = False
        if self.snap_u in self.retain:
            self.kept[self.snap_u] = self.snap

    # -- set-up -----------------------------------------------------------

    def _warm_queries(self):
        """Compile (or load) every query program the window uses: each
        op at each padded batch size in the mix's ``warm_rows``; range
        counts over several box sets, so that the engine's row buckets
        have converged before the window."""
        side, j = self.queries.side, 0
        for rows in self.mix["warm_rows"]:
            for rnd in range(self.mix["warm_rounds"]):
                q = self.queries.points(_WARM, j, rows)
                lo = self.queries.lows(_WARM, j + 1, rows)
                j += 2
                ts = [self.batcher.submit_knn(q[i], self.k,
                                              impl=self.knn_impl)
                      for i in range(rows)] if rnd == 0 else []
                self.batcher.flush()
                ts += [self.batcher.submit_range_count(lo[i],
                                                       lo[i] + side - 1)
                       for i in range(rows)]
                self.batcher.flush()
                _block([t.result() for t in ts])

    # -- the window -------------------------------------------------------

    def window(self, seconds: float):
        self.seconds = seconds
        mix, ann = self.mix, self.ann
        sch = Schedule(self.seed, mix, self.queries)
        sch.extend_to(seconds + 10.0)
        self.sch = sch
        self.counted = int(np.searchsorted(sch.t, seconds))
        cap = len(sch.t)
        self.sub_t = np.full(cap, np.nan)
        self.flush_t = np.full(cap, np.nan)
        self.done_t = np.full(cap, np.nan)
        self.ver = np.full(cap, -1, np.int64)
        self.answers = {}
        self.flush_count = {op: 0 for op in OPS}
        self.flush_rows: dict = {}
        self.stalls: list = []
        every = mix["update_every_s"]
        # versions whose snapshots are kept for the check: ids in a kNN
        # answer index the tree it was answered on. Two versions drawn
        # from the seed among those that serve a whole tick
        ticks = max(1, int(seconds // every))
        first = self.snap_u
        self.retain = {first + int(j) for j in np.random.default_rng(
            [self.seed, 8]).choice(ticks, min(2, ticks), replace=False)}
        if first in self.retain:
            self.kept[first] = self.snap
        delay = mix["max_delay_ms"] / 1e3
        done_q: queue.Queue = queue.Queue()
        waiter = threading.Thread(target=self._complete, args=(done_q,),
                                  daemon=True)
        waiter.start()
        rec0 = self.srv.stats["recoveries"]
        pending: list = []
        i = 0
        flushed = 0           # counted requests flushed so far
        self.updates = 0
        try:
            with ann("bench.window"):
                t0 = self.t0 = clock()
                next_upd = t0 + every
                while flushed < self.counted:
                    now = clock()
                    if now >= next_upd:
                        with ann("bench.update"):
                            if self.dispatched:
                                self._commit_update()
                            self._dispatch_update()
                        self._note("update", now)
                        self.updates += 1
                        next_upd += every
                        continue
                    while i < len(sch.t) and t0 + sch.t[i] <= now:
                        if i + 1 >= len(sch.t):
                            self._grow(sch.extend_to(sch.t[-1] + 10.0))
                        t_call = clock()
                        with ann("bench.submit"):
                            pending.append((i, self._submit(sch, i)))
                        self.sub_t[i] = t_call
                        i += 1
                        self._note("submit", t_call)
                        flushed += self._harvest(pending, t_call, done_q)
                    t_call = clock()
                    with ann("bench.poll"):
                        self.batcher.poll()
                    self._note("poll", t_call)
                    flushed += self._harvest(pending, t_call, done_q)
                    if flushed >= self.counted:
                        break
                    nxt = min(t0 + sch.t[i], next_upd)
                    if pending:
                        nxt = min(nxt, self.sub_t[pending[0][0]] + delay)
                    dt = nxt - clock()
                    if dt > 0:
                        with ann("bench.wait"):
                            time.sleep(dt)
                done_q.put(None)
                waiter.join()
        finally:
            if waiter.is_alive():
                done_q.put(None)
                waiter.join()
        if self.dispatched:
            self._commit_update()
        self.recoveries = self.srv.stats["recoveries"] - rec0

    def _note(self, kind: str, t_call: float):
        """Keep the main thread's longest blocks, for the run's lines."""
        d = clock() - t_call
        if d > 0.05:
            self.stalls.append((round(d, 6), kind, round(t_call - self.t0, 3)))

    def _grow(self, cap: int):
        for name in ("sub_t", "flush_t", "done_t"):
            a = getattr(self, name)
            setattr(self, name, np.concatenate(
                [a, np.full(cap - len(a), np.nan)]))
        self.ver = np.concatenate([self.ver, np.full(cap - len(self.ver),
                                                     -1, np.int64)])

    def _submit(self, sch, i):
        if OPS[sch.op[i]] == "knn":
            return self.batcher.submit_knn(sch.qpts[i], self.k,
                                           impl=self.knn_impl)
        lo, hi = sch.box(i)
        return self.batcher.submit_range_count(lo, hi)

    def _harvest(self, pending: list, t_call: float, done_q) -> int:
        """After a batcher call: a flush resolves every pending ticket at
        once. Hand them to the waiter, one item per op in the order the
        batcher ran the ops (the order each op was first queued)."""
        if not pending or not pending[0][1].done:
            return 0
        by_op: dict = {}
        for idx, t in pending:
            by_op.setdefault(self.sch.op[idx], []).append((idx, t.result()))
        for op, items in by_op.items():
            self.flush_count[OPS[op]] += 1
            rows = f"{OPS[op]}:{1 << (len(items) - 1).bit_length()}"
            self.flush_rows[rows] = self.flush_rows.get(rows, 0) + 1
            idx = np.array([x[0] for x in items])
            self.flush_t[idx] = t_call
            self.ver[idx] = self.snap_u
            done_q.put(items)
        n = sum(1 for idx, _ in pending if idx < self.counted)
        pending.clear()
        return n

    def _complete(self, done_q):
        """Waiter thread: stamps each flushed request when its result is
        on the host, in the order the device runs them."""
        while True:
            items = done_q.get()
            if items is None:
                return
            _block([v for _, v in items])
            t = clock()
            for idx, v in items:
                self.done_t[idx] = t
                # only answers the check can read are kept alive
                if idx < self.counted and self.ver[idx] in self.retain:
                    self.answers[idx] = v

    # -- results --------------------------------------------------------

    def _counted(self, op: str):
        n = self.counted
        return np.flatnonzero(self.sch.op[:n] == OPS.index(op))

    def _from_due_ms(self, op: str, stamps):
        idx = self._counted(op)
        idx = idx[~np.isnan(stamps[idx])]
        return np.array(stats.latencies_ms(self.t0 + self.sch.t[idx],
                                           stamps[idx]))

    def latencies_ms(self, op: str):
        """Due time to result on the host, per answered request."""
        return self._from_due_ms(op, self.done_t)

    def queue_wait_ms(self, op: str):
        """Due time to the start of the flush that carried it."""
        return self._from_due_ms(op, self.flush_t)

    def attempted(self) -> int:
        return self.counted

    def failed(self) -> int:
        return int(np.isnan(self.done_t[:self.counted]).sum())

    def info(self) -> dict:
        n = self.counted
        late = 1e3 * (self.sub_t[:n] - (self.t0 + self.sch.t[:n]))
        out = {"requests": n, "updates_in_window": self.updates,
               "recoveries_in_window": self.recoveries,
               "flushes": self.flush_count,
               "padded_rows": self.flush_rows,
               "longest_blocks_s": sorted(self.stalls, reverse=True)[:5],
               "generator_late_ms": {
                   "p50": float(np.nanpercentile(late, 50)),
                   "p95": float(np.nanpercentile(late, 95)),
                   "max": float(np.nanmax(late))}}
        for op in OPS:
            lat = self.latencies_ms(op)
            out[op] = {"n": int(len(lat)),
                       "p50_ms": float(np.percentile(lat, 50)),
                       "p95_ms": float(np.percentile(lat, 95))}
        return out

    def check(self, control: bool = False) -> dict:
        """Answers from the timed path, checked against the snapshot
        each was answered on, and the acknowledged writes read back.

        A sample drawn from the seed of the kNN and range requests
        answered on the kept versions is compared with the brute force
        over that version's live set, rebuilt from the stream; the
        committed head's live multiset with the stream's window. The
        control puts the bfloat16 brute force in the program's place
        and leaves out the last acknowledged insert."""
        hi = self.cfg["hi"]
        rng = np.random.default_rng([self.seed, 9])
        answered = np.array(sorted(self.answers), np.int64)
        out = {"unanswered": self.failed()}
        wrong = {op: 0 for op in OPS}
        for u, snap in sorted(self.kept.items()):
            lv = ref.LiveSet(self.stream.live(u), hi)
            pts, ok = snap.index.extract_points()
            tree = np.asarray(pts), np.asarray(ok)
            on_u = answered[self.ver[answered] == u]
            for op in OPS:
                cand = on_u[self.sch.op[on_u] == OPS.index(op)]
                n = min(len(cand), -(-self.mix["check_sample"]
                                     // len(self.kept)))
                for i in rng.choice(cand, n, replace=False):
                    if op == "knn" and control:
                        good = knn_ok_lowp(lv, self.sch.qpts[i], self.k)
                    elif op == "knn":
                        good = knn_ok(lv, tree, self.sch.qpts[i], self.k,
                                      self.answers[i])
                    else:
                        lo, hb = self.sch.box(i)
                        got = (ref.range_count_lowp(lv.pts, lo, hb)
                               if control
                               else int(np.asarray(self.answers[i])[0]))
                        good = got == lv.range_count(lo, hb)
                    wrong[op] += not good
        out["knn_wrong"] = wrong["knn"]
        out["range_wrong"] = wrong["range_count"]
        want = self.stream.live(self.u)
        if control:
            got = want[:-self.batch]
        else:
            pts, ok = self.srv.head_index.extract_points()
            got = np.asarray(pts)[np.asarray(ok)]
        out["live_diff"] = ref.multiset_diff(got, want, hi)
        return out
