"""The general traffic generator: points, updates, arrivals and query
payloads drawn from ``--seed`` by the parameters of a configuration's
and a traffic mix's files.

Everything is made on the host with numpy, in bulk, from
``numpy.random.default_rng([seed, stream, index])``, so the same seed
gives the same inputs, any whole seed works (also one above 2**31),
and nothing here depends on the program under test.

Each part is chosen by a ``kind`` in the files, so a new deployment is
new files only:

* data (the configuration's ``data``): ``uniform``, or ``varden``
  (random walks with restarts: clusters of varying density, after Gan
  and Tao, as the paper's Varden);
* updates (the mix's ``updates``): ``sliding_window`` (delete the
  oldest chunk, insert the next), or ``moving`` (objects chosen from
  the seed report a new position near the old one: a delete and an
  insert);
* arrivals (the mix's ``arrivals``): ``poisson``, or ``bursty``
  (requests in bursts of ``burst`` at one instant);
* query points and boxes (the mix's ``queries``): ``uniform``, or
  ``hot`` (near ``centres`` points of the initial live set, picked with
  Zipf weights of exponent ``zipf_s``, within ``spread`` per
  coordinate).
"""

from __future__ import annotations

import numpy as np

# stream ids for default_rng([seed, stream, index])
_POINTS, _GAPS, _OPS, _QUERIES, _MOVES, _CENTRES = 0, 1, 2, 3, 6, 7

OPS = ("knn", "range_count")
UNIFORM = {"kind": "uniform"}


def uniform(seed: int, stream: int, index: int, n: int, dim: int,
            hi: int) -> np.ndarray:
    """``n`` i.i.d. uniform int32 points in ``[0, hi)^dim`` (the paper's
    Uniform distribution, Sec. 5.1)."""
    rng = np.random.default_rng([seed, stream, index])
    return rng.integers(0, hi, (n, dim), dtype=np.int32)


def varden(seed: int, stream: int, index: int, n: int, dim: int, hi: int,
           step: int, restart_p: float) -> np.ndarray:
    """``n`` points of random walks in ``[0, hi)^dim``: each point moves
    the last by up to ``step`` per coordinate, and with probability
    ``restart_p`` (always at the first point) jumps to a uniform one
    instead. Positions are clipped to the domain."""
    rng = np.random.default_rng([seed, stream, index])
    steps = rng.integers(-step, step + 1, (n, dim), dtype=np.int64)
    restart = rng.random(n) < restart_p
    restart[0] = True
    start = rng.integers(0, hi, (n, dim), dtype=np.int64)
    heads = np.flatnonzero(restart)[np.cumsum(restart) - 1]
    walk = np.cumsum(steps, axis=0)
    pos = start[heads] + walk - walk[heads]
    return np.clip(pos, 0, hi - 1).astype(np.int32)


def points(data: dict, seed: int, stream: int, index: int, n: int,
           dim: int, hi: int) -> np.ndarray:
    """``n`` points of the configuration's distribution."""
    if data["kind"] == "uniform":
        return uniform(seed, stream, index, n, dim, hi)
    if data["kind"] == "varden":
        return varden(seed, stream, index, n, dim, hi, int(data["step"]),
                      float(data["restart_p"]))
    raise ValueError(f"unknown data kind {data['kind']!r}")


class SlidingWindow:
    """A sliding-window stream in chunks of ``batch``.

    Chunk ``i`` is drawn from ``(seed, i)`` alone. The live set after
    ``u`` steps of "delete the oldest chunk, insert the next one" is
    chunks ``u .. u + chunks - 1``: a constant-size window."""

    def __init__(self, seed: int, data: dict, n: int, batch: int, dim: int,
                 hi: int):
        if n % batch:
            raise ValueError(f"window {n} is not a whole number of "
                             f"{batch}-point chunks")
        self.seed, self.data, self.batch = seed, data, batch
        self.dim, self.hi = dim, hi
        self.chunks = n // batch

    def chunk(self, i: int) -> np.ndarray:
        return points(self.data, self.seed, _POINTS, i, self.batch,
                      self.dim, self.hi)

    def live(self, steps: int) -> np.ndarray:
        """The live points after ``steps`` steps."""
        return np.concatenate([self.chunk(i) for i in
                               range(steps, steps + self.chunks)])

    def step(self, u: int):
        """Step ``u``: (points deleted, points inserted)."""
        return self.chunk(u), self.chunk(u + self.chunks)


class MovingObjects:
    """``n`` objects, placed as the configuration's data; at each step
    ``batch`` distinct objects drawn from ``(seed, step)`` move by up to
    ``disp`` per coordinate (clipped to the domain): the index deletes
    their old positions and inserts the new ones."""

    def __init__(self, seed: int, data: dict, n: int, batch: int, dim: int,
                 hi: int, disp: int):
        if batch > n:
            raise ValueError(f"{batch} moves per step exceed {n} objects")
        self.seed, self.n, self.batch = seed, n, batch
        self.dim, self.hi, self.disp = dim, hi, disp
        self.chunks = -(-n // batch)
        self.pos0 = np.concatenate([
            points(data, seed, _POINTS, i, batch, dim, hi)
            for i in range(self.chunks)])[:n]
        self._u, self._pos = 0, self.pos0.copy()

    def _move(self, u: int):
        rng = np.random.default_rng([self.seed, _MOVES, u])
        sel = rng.choice(self.n, self.batch, replace=False)
        d = rng.integers(-self.disp, self.disp + 1, (self.batch, self.dim))
        return sel, np.clip(self._pos[sel] + d, 0, self.hi - 1).astype(
            np.int32)

    def _at(self, u: int) -> np.ndarray:
        """Positions after ``u`` steps, carried forward from the last
        asked-for step (or replayed from the start)."""
        if u < self._u:
            self._u, self._pos = 0, self.pos0.copy()
        while self._u < u:
            sel, new = self._move(self._u)
            self._pos[sel] = new
            self._u += 1
        return self._pos

    def live(self, steps: int) -> np.ndarray:
        return self._at(steps).copy()

    def step(self, u: int):
        old = self._at(u)
        sel, new = self._move(u)
        return old[sel].copy(), new


def make(cfg: dict, mix: dict, seed: int, batch: int):
    """The update stream of a configuration under a mix."""
    data = cfg.get("data", UNIFORM)
    upd = mix.get("updates", {"kind": "sliding_window"})
    args = (seed, data, cfg["n"], batch, cfg["dim"], cfg["hi"])
    if upd["kind"] == "sliding_window":
        return SlidingWindow(*args)
    if upd["kind"] == "moving":
        return MovingObjects(*args, int(upd["disp"]))
    raise ValueError(f"unknown updates kind {upd['kind']!r}")


class Queries:
    """Query points and range boxes of a mix (``queries``, ``k``,
    ``box_side_share``). ``live0`` is the initial live set, from which
    ``hot`` centres are drawn."""

    def __init__(self, seed: int, mix: dict, dim: int, hi: int,
                 live0: np.ndarray):
        self.seed, self.dim, self.hi = seed, dim, hi
        self.spec = mix.get("queries", UNIFORM)
        self.side = int(hi * mix["box_side_share"])
        if self.spec["kind"] == "hot":
            c = int(self.spec["centres"])
            rng = np.random.default_rng([seed, _CENTRES])
            self.centres = live0[rng.choice(len(live0), c, replace=False)]
            w = np.arange(1, c + 1, dtype=np.float64) ** -float(
                self.spec["zipf_s"])
            self.weights = w / w.sum()
        elif self.spec["kind"] != "uniform":
            raise ValueError(f"unknown queries kind {self.spec['kind']!r}")

    def _hot(self, stream: int, index: int, n: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, stream, index])
        r = int(self.spec["spread"])
        c = self.centres[rng.choice(len(self.centres), n, p=self.weights)]
        d = rng.integers(-r, r + 1, (n, self.dim))
        return np.clip(c + d, 0, self.hi - 1).astype(np.int32)

    def points(self, stream: int, index: int, n: int) -> np.ndarray:
        """``n`` kNN query points."""
        if self.spec["kind"] == "uniform":
            return uniform(self.seed, stream, index, n, self.dim, self.hi)
        return self._hot(stream, index, n)

    def lows(self, stream: int, index: int, n: int) -> np.ndarray:
        """Low corners of ``n`` square boxes of side ``side``; box ``i``
        is ``[lo, lo + side - 1]``, corners included."""
        if self.spec["kind"] == "uniform":
            return uniform(self.seed, stream, index, n, self.dim,
                           self.hi - self.side)
        p = self._hot(stream, index, n).astype(np.int64) - self.side // 2
        return np.clip(p, 0, self.hi - self.side).astype(np.int32)


class Schedule:
    """Open-loop arrivals at a fixed mean rate, with payloads.

    Arrivals come in blocks of ``block``. Every block has the same gaps
    and the same count of each op, in an order drawn from the seed. So
    every seed offers exactly the same load in another order, and runs
    on different seeds differ no more than the order makes them.
    ``poisson`` gaps are the midpoint quantiles of an exponential law
    scaled to mean ``1 / rate``; ``bursty`` puts ``burst`` requests at
    one instant, with such gaps of mean ``burst / rate`` between
    bursts."""

    def __init__(self, seed: int, mix: dict, queries: Queries,
                 block: int = 1000):
        self.seed, self.queries, self.block = seed, queries, block
        self.rate = float(mix["rate_per_s"])
        counts = [round(mix["shares"][op] * block) for op in OPS]
        if sum(counts) != block:
            raise ValueError(f"op shares {mix['shares']} do not split a "
                             f"block of {block}")
        self._ops = np.repeat(np.arange(len(OPS)), counts)
        arr = mix.get("arrivals", {"kind": "poisson"})
        self.burst = int(arr.get("burst", 1)) if arr["kind"] == "bursty" \
            else 1
        if arr["kind"] not in ("poisson", "bursty") or block % self.burst:
            raise ValueError(f"arrivals {arr} do not fit a block of "
                             f"{block}")
        bursts = block // self.burst
        q = (np.arange(bursts) + 0.5) / bursts
        gaps = -np.log1p(-q)
        self._gaps = gaps * (block / (self.rate * gaps.sum()))
        self.side = queries.side
        dim = queries.dim
        self.t = np.zeros(0)
        self.op = np.zeros(0, np.int64)
        self.qpts = np.zeros((0, dim), np.int32)
        self.lo = np.zeros((0, dim), np.int32)
        self._t_end = 0.0

    def extend_to(self, horizon_s: float) -> int:
        """Draw whole blocks until arrivals reach ``horizon_s``; returns
        the number of arrivals now drawn."""
        ts, ops, qs, los = [self.t], [self.op], [self.qpts], [self.lo]
        blk = len(self.t) // self.block
        while self._t_end <= horizon_s:
            g = np.random.default_rng([self.seed, _GAPS, blk]).permutation(
                self._gaps)
            if self.burst > 1:
                g = np.repeat(g, self.burst)
                g[np.arange(len(g)) % self.burst != 0] = 0.0
            t = self._t_end + np.cumsum(g)
            self._t_end = float(t[-1])
            ts.append(t)
            ops.append(np.random.default_rng(
                [self.seed, _OPS, blk]).permutation(self._ops))
            qs.append(self.queries.points(_QUERIES, 2 * blk, self.block))
            los.append(self.queries.lows(_QUERIES, 2 * blk + 1, self.block))
            blk += 1
        self.t, self.op = np.concatenate(ts), np.concatenate(ops)
        self.qpts, self.lo = np.concatenate(qs), np.concatenate(los)
        return len(self.t)

    def box(self, i: int):
        """Request ``i``'s square box, inclusive corners."""
        return self.lo[i], self.lo[i] + (self.side - 1)
