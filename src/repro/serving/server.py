"""``SpatialServer``: a versioned spatial index with snapshot-isolated
queries and pipelined (async-dispatched) updates.

The trees behind :class:`repro.core.SpatialIndex` are functional —
every update returns a new handle and never mutates the old one — so a
*snapshot* is free: it is just a reference to version ``v``'s handle.
The server exploits that plus JAX async dispatch to overlap updates and
queries with **no barrier between them**:

* ``insert``/``delete`` dispatch version ``v+1``'s jit-cached update
  closure and return immediately (dynamic backends only enqueue device
  work; rebuild-style kd/zd stay synchronous — their size verification
  needs a host read). The facade's usual host-side ``overflowed`` read
  — a full device sync — is **deferred**: the flag is sticky across
  updates (spac/porth carry it forward), so one read at the next sync
  point covers every update since the last known-good version.
* ``snapshot()`` hands out an immutable :class:`Snapshot` of any
  retained version; queries against it are answered from exactly that
  version's tree even while later updates are in flight on device
  (asserted bit-for-bit in tests/test_serving.py).
* A **bounded version window** (``window=``) is the backpressure knob:
  publishing version ``v+1`` evicts version ``v-window`` and blocks on
  it, so at most ``window`` updates are ever in flight and device queue
  depth (and retained-tree memory) stays bounded.
* ``commit()`` is the explicit barrier: it blocks on the head version,
  performs the deferred overflow check, and reclaims old versions. If
  any deferred insert overflowed, the server **replays the op log from
  the last good version** through the facade's synchronous
  grow->retry->compact recovery, so a committed head always holds the
  exact multiset of every op applied in order — callers never lose
  points. (Size the server with ``capacity_points=`` for the lifetime
  maximum and replay never triggers; ``stats["recoveries"]`` counts it.)

Snapshot isolation requires old versions' buffers to stay live, so the
server refuses a ``donate=True`` index — the bounded window replaces
donation as the memory-control mechanism.

The same lineage fronts a mesh-sharded head
(:class:`repro.core.index.DistributedIndex`, ``build(..., mesh=)``):
updates dispatch through the cached shard_map exchange and queries
through the engine's distributed merge, both version-functional, so
snapshots/window/commit work unchanged. Distribution adds a second
deferred failure signal next to sticky ``overflowed`` (now a per-shard
vector, reduced with :func:`_overflowed`): the routing slab's
``dropped`` counter. Both are checked at the same sync points and both
trigger the same commit-time replay — see tests/test_serving_distributed.py
and ROADMAP "Distributed serving (PR 10)".
"""

from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core import make_index
from ..core.index import DistributedIndex, SpatialIndex


def _overflowed(tree) -> bool:
    """Deferred sticky-overflow read, shape-agnostic: scalar flag on a
    local tree, per-shard (n_shards,) vector on a distributed head (any
    shard overflowing dirties the version)."""
    flag = getattr(tree, "overflowed", None)
    return flag is not None and bool(jnp.any(flag))


class Snapshot:
    """Immutable view of one server version; queries delegate to the
    underlying :class:`SpatialIndex` (same engine, same cached plans)
    and are isolated from every later update."""

    __slots__ = ("version", "index")

    def __init__(self, version: int, index: SpatialIndex):
        self.version = version
        self.index = index

    def knn(self, qpts, k: int, *, impl: str = "auto"):
        return self.index.knn(qpts, k, impl=impl)

    def knn_points(self, qpts, k: int, *, impl: str = "auto"):
        return self.index.knn_points(qpts, k, impl=impl)

    def range_count(self, lo, hi):
        return self.index.range_count(lo, hi)

    def range_list(self, lo, hi):
        return self.index.range_list(lo, hi)

    @property
    def size(self):
        return self.index.size

    def __len__(self) -> int:
        return len(self.index)

    def __repr__(self):
        return f"Snapshot(version={self.version}, kind={self.index.kind!r})"


class SpatialServer:
    """Owns a lineage of :class:`SpatialIndex` versions; see the module
    docstring for the pipelining/backpressure/commit contract."""

    def __init__(self, index: SpatialIndex, *, window: int = 4):
        if getattr(index, "_donate", False):
            raise ValueError(
                "SpatialServer requires a non-donating index: snapshots "
                "keep old versions' buffers live, which donate=True would "
                "hand to the next update; the bounded version window "
                "(window=) bounds memory instead")
        if window < 1:
            raise ValueError("window must be >= 1")
        self.window = int(window)
        self._versions: OrderedDict[int, SpatialIndex] = OrderedDict()
        self._head = 0
        self._versions[0] = index
        # memory accounting: bytes per retained version from leaf
        # ``nbytes`` metadata (shape/dtype arithmetic — no device read,
        # see repro.obs.memory), plus window aggregates. peak_window
        # is the high-water mark of retained bytes; evicted_* count
        # window-pressure evictions only (commit-time reclamation is a
        # barrier, not backpressure).
        nb = obs.tree_bytes(index.tree)
        self._version_bytes: dict[int, int] = {0: nb}
        self.mem = {"live_bytes": nb, "window_bytes": nb,
                    "peak_window_bytes": nb, "evicted_bytes": 0,
                    "evictions": 0}
        # recovery state: the last version whose (sticky) overflow flag
        # was read clean, plus every op dispatched since
        self._base = 0
        self._base_index = index
        # distributed heads add a second sticky failure signal: the
        # routing-slab `dropped` counter. Construction is a sync point,
        # so reading the baseline here is free; dispatch paths only ever
        # compare against it at eviction/commit barriers.
        self._distributed = isinstance(index, DistributedIndex)
        self._base_dropped = (int(index.dropped) if self._distributed
                              else 0)
        self._log: list[tuple[str, object, object]] = []
        self.stats = {"inserts": 0, "deletes": 0, "commits": 0,
                      "recoveries": 0, "update_points": 0}
        # device-side row counts not yet folded into update_points;
        # commit() (already a barrier) reads them off-device
        self._deferred_points: list = []

    @classmethod
    def build(cls, kind: str, points, *, window: int = 4, **make_kw):
        """Build a fresh index via :func:`repro.core.make_index` and wrap
        it; pass ``capacity_points=`` for the lifetime maximum so the
        deferred overflow check never trips."""
        if make_kw.get("donate"):
            raise ValueError("SpatialServer does not support donate=True")
        return cls(make_index(kind, points, **make_kw), window=window)

    # -- introspection -----------------------------------------------------

    @property
    def head_version(self) -> int:
        return self._head

    @property
    def head_index(self) -> SpatialIndex:
        return self._versions[self._head]

    @property
    def versions(self) -> tuple[int, ...]:
        """Retained version ids, oldest first."""
        return tuple(self._versions)

    @property
    def in_flight(self) -> int:
        """Updates dispatched since the last commit (upper bound on
        device work not yet known complete)."""
        return self._head - self._base

    def snapshot(self, version: int | None = None) -> Snapshot:
        """A consistent view of ``version`` (default: head). Raises
        ``KeyError`` for versions outside the retained window."""
        v = self._head if version is None else int(version)
        try:
            return Snapshot(v, self._versions[v])
        except KeyError:
            raise KeyError(
                f"version {v} not retained (window holds "
                f"{list(self._versions)})") from None

    # -- updates (async dispatch) ------------------------------------------

    def _live_rows(self, pts, mask) -> int:
        """Rows contributed to ``stats["update_points"]`` — without a
        device sync on the dispatch path. A device mask is summed *on
        device* and folded into the stat at the next ``commit()`` (a
        barrier anyway), so ``update_points`` is exact at sync points
        and a lower bound between them."""
        if mask is None:
            return int(pts.shape[0])
        if isinstance(mask, jax.Array):
            self._deferred_points.append(jnp.sum(mask, dtype=jnp.int32))
            return 0
        # host-side mask: popcount without touching the device
        return int(np.count_nonzero(mask))

    def insert(self, pts, mask=None) -> int:
        """Dispatch a batch insert as version ``head+1``; returns the new
        version id without waiting for the device (dynamic backends)."""
        with obs.span("serving.insert") as sp:
            pts = jnp.asarray(pts)
            sp.set(rows=pts.shape[0], version=self._head + 1)
            new = self.head_index.insert_unchecked(pts, mask)
            self.stats["inserts"] += 1
            self.stats["update_points"] += self._live_rows(pts, mask)
            return self._publish(new, ("insert", pts, mask))

    def delete(self, pts, mask=None) -> int:
        """Dispatch a batch delete as version ``head+1`` (deletes never
        overflow rows; distributed heads defer their routing-slab
        ``dropped`` check, so dispatch stays async there too)."""
        with obs.span("serving.delete") as sp:
            pts = jnp.asarray(pts)
            sp.set(rows=pts.shape[0], version=self._head + 1)
            new = self.head_index.delete_unchecked(pts, mask)
            self.stats["deletes"] += 1
            self.stats["update_points"] += self._live_rows(pts, mask)
            return self._publish(new, ("delete", pts, mask))

    def _publish(self, index: SpatialIndex, op: tuple) -> int:
        self._head += 1
        self._versions[self._head] = index
        self._log.append(op)
        nb = obs.tree_bytes(index.tree)       # metadata only, no sync
        self._version_bytes[self._head] = nb
        mem = self.mem
        mem["live_bytes"] = nb
        mem["window_bytes"] += nb
        while len(self._versions) > self.window:
            v, old = self._versions.popitem(last=False)
            freed = self._version_bytes.pop(v, 0)
            mem["window_bytes"] -= freed
            mem["evicted_bytes"] += freed
            mem["evictions"] += 1
            obs.count("server.mem.evicted_bytes", freed)
            obs.count("server.mem.evictions")
            # backpressure: everything up to the evicted version must be
            # done before more updates pile on; its (now free) overflow
            # read doubles as an early deferred check
            with obs.span("serving.evict_block", version=v):
                # contract: allow[host-sync-in-dispatch] window eviction
                # is the designed backpressure point; waiting on the
                # *evicted* version bounds device-queue depth without
                # stalling head
                jax.block_until_ready(old.tree)
            # past the barrier both sticky reads are free; a distributed
            # version is dirty if any shard overflowed OR the routing
            # slab dropped entries since the last clean baseline
            dirty = _overflowed(old.tree) or (
                self._distributed
                and int(old.dropped) != self._base_dropped)
            if dirty:
                self._recover()
            elif v > self._base:
                # fast-forward the recovery base: ops up to v are clean
                del self._log[: v - self._base]
                self._base, self._base_index = v, old
        if mem["window_bytes"] > mem["peak_window_bytes"]:
            mem["peak_window_bytes"] = mem["window_bytes"]
        obs.gauge("server.mem.live_bytes", mem["live_bytes"])
        obs.gauge("server.mem.window_bytes", mem["window_bytes"])
        return self._head

    # -- sync points -------------------------------------------------------

    def commit(self) -> int:
        """Barrier: wait for the head version, run the deferred overflow
        check (replaying from the last good version on overflow), and
        reclaim every older version. Returns the committed version id.
        Its phases are the ``serving.commit.*`` obs spans."""
        with obs.span("serving.commit") as sp:
            sp.set(version=self._head, in_flight=self._head - self._base)
            head = self._versions[self._head]
            with obs.span("serving.commit.wait"):
                jax.block_until_ready(head.tree)
            with obs.span("serving.commit.check"):
                # past the barrier these reads are free; see _live_rows
                dirty = _overflowed(head.tree) or (
                    self._distributed
                    and int(head.dropped) != self._base_dropped)
                self.stats["update_points"] += sum(
                    int(x) for x in self._deferred_points)
                self._deferred_points = []
            if dirty:
                # the replay also resets the routing-slab baseline
                head = self._recover()
            with obs.span("serving.commit.reclaim"):
                self._base, self._base_index = self._head, head
                self._log = []
                self._versions = OrderedDict({self._head: head})
                self._rebase_memory(head)
            self.stats["commits"] += 1
            # commit is THE barrier: deferred obs device reads (span
            # attachments, deferred counters and gauges) resolve here
            # for free
            with obs.span("serving.commit.resolve"):
                if self._distributed and obs.enabled():
                    self._defer_shard_gauges(head)
                obs.resolve()
            return self._head

    @staticmethod
    def _defer_shard_gauges(head: DistributedIndex) -> None:
        """Per-shard balance of a mesh-sharded head, as gauges read at
        the next resolve: live points per shard and the largest over
        the mean (the straggler signal of key-range routing)."""
        sizes = head.shard_sizes()
        for i in range(sizes.shape[0]):
            obs.defer_gauge(f"server.shard{i}.live_points", sizes[i])
        obs.defer_gauge("server.shard.max_over_mean",
                        jnp.max(sizes) / jnp.maximum(jnp.mean(sizes), 1))

    def _recover(self) -> SpatialIndex:
        """Replay the op log from the last good version through the
        facade's synchronous recovery path (grow -> retry -> compact),
        making the head exact again after a deferred overflow."""
        with obs.span("serving.replay", ops=len(self._log),
                      base=self._base, head=self._head):
            idx = self._base_index
            for op, pts, mask in self._log:
                idx = (idx.insert(pts, mask) if op == "insert"
                       else idx.delete(pts, mask))
            jax.block_until_ready(idx.tree)
        self._versions = OrderedDict({self._head: idx})
        self._base, self._base_index = self._head, idx
        if self._distributed:
            # the replayed head is the new clean baseline for the
            # routing-slab counter (checked ops guarantee no new drops,
            # but a mid-replay re-shard resets the cumulative count)
            self._base_dropped = int(idx.dropped)
        self._log = []
        self._rebase_memory(idx)
        self.stats["recoveries"] += 1
        return idx

    # -- memory accounting -------------------------------------------------

    def _rebase_memory(self, index: SpatialIndex) -> None:
        """The window just collapsed to head only (commit/recover):
        recompute the byte aggregates from the surviving version."""
        nb = obs.tree_bytes(index.tree)
        self._version_bytes = {self._head: nb}
        mem = self.mem
        mem["live_bytes"] = nb
        mem["window_bytes"] = nb
        if nb > mem["peak_window_bytes"]:
            mem["peak_window_bytes"] = nb
        obs.gauge("server.mem.live_bytes", nb)
        obs.gauge("server.mem.window_bytes", nb)

    def memory_report(self) -> dict:
        """Copy of the byte aggregates plus per-retained-version bytes.
        All values come from array metadata — calling this never syncs
        the device, so it is safe between commits."""
        return {**self.mem,
                "version_bytes": dict(self._version_bytes),
                "retained": len(self._versions)}

    def __repr__(self):
        return (f"SpatialServer(kind={self.head_index.kind!r}, "
                f"head={self._head}, window={self.window}, "
                f"retained={len(self._versions)})")
