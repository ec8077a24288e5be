"""Query micro-batcher: coalesce kNN/range requests into pow2-padded
batches that hit the QueryEngine's jit-cached plans.

Serving traffic arrives as many small requests (a handful of query
points each), but the :class:`repro.core.engine.QueryEngine` caches its
jitted query plans on the *batch* signature ``(op, Q-shape, dtype,
k/caps, impl)`` — the same signature-keying pattern as
``repro.core.index._update_closure`` and ``repro.serve.engine``'s
prefill/decode closures. Dispatching each request alone would retrace
per distinct request size and waste the accelerator on tiny launches.

The :class:`MicroBatcher` instead queues requests per plan signature
``(op, k, dim, dtype, impl)``, concatenates them, and **pads the
coalesced batch to the next power of two** (replicating the final row —
rows are independent under vmap, so padding never perturbs real
answers). Batched answers are split back per request (below), and
because every engine impl is exact and canonically (d2, id)-ordered, kNN
and range-count answers **bit-match the answers the same requests would get
dispatched alone** (asserted in tests/test_serving.py); range-list
answers match in counts and id *sets*, but the padded id width is
sized by the coalesced batch's largest output, so it can exceed the
solo-dispatch width. Pow2 padding means a workload with arbitrary
ragged request sizes visits at most O(log max_batch) distinct Q shapes,
so the engine's plan cache converges after warmup (also asserted, via
``repro.core.engine.trace_count``).

Admission policy (cooperative — there is no background timer thread):
a flush is forced when pending rows reach ``max_batch``, or when the
oldest queued request has waited ``max_delay_s`` *as observed at the
next interaction point* — a ``submit``, an explicit ``poll()``, or a
``Ticket.result()`` (which always flushes whatever is pending, so no
request waits forever). ``max_delay_s=0`` disables coalescing-by-wait:
every submit flushes immediately. Trickle traffic that only polls
``Ticket.done`` should call ``poll()`` in its wait loop.

Splitting a flushed group's answers (the ``batcher.split`` span) costs
one device dispatch, not one per output per request, where it can: when
every request of the group has one row and every output is a device
array, all outputs are split into their rows by one jitted launch (one
program per pow2 bucket and output signature, so it compiles with the
group's query plans) and request i takes row i of each; pad rows are
dropped. This is the ``batcher.split.fused`` counter. Groups with
ragged requests, or with host (numpy) outputs, where a slice costs
nothing, are sliced request by request (``batcher.split.sliced``).
Either way each request gets the same device arrays, of the same
shapes, dtypes and values, and the split reads nothing back from the
device.

Requests submitted as host (numpy) rows stay host-side until flush —
one concatenate + one device transfer per coalesced batch — while
device-array requests are concatenated on device; the two never race
because grouping is per plan signature.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.engine import _pow2


def _as_rows(x):
    """Normalize one request payload to a 2-D row batch, keeping host
    arrays on host (device transfer is deferred to the flush)."""
    if isinstance(x, jax.Array):
        return jnp.atleast_2d(x)
    # contract: allow[host-sync-in-dispatch] this branch only ever sees
    # host payloads (device arrays returned above); np.asarray here is a
    # host-side copy, not a device read
    return np.atleast_2d(np.asarray(x))


def _concat_pad(parts, rows: int):
    """Concatenate request payloads and pad to the next pow2 row count
    by replicating the last row (rows are independent under vmap, so
    pad rows cannot perturb real answers)."""
    xp = jnp if any(isinstance(p, jax.Array) for p in parts) else np
    col = xp.concatenate(parts)
    pad = _pow2(rows) - rows
    if pad:
        col = xp.concatenate([col, xp.repeat(col[-1:], pad, axis=0)])
    return col


@jax.jit
def _split_rows(*outs):
    """Every row of every output as its own ``(1, ...)`` array, in one
    launch: ``_split_rows(*outs)[j][i]`` is row i of ``outs[j]``."""
    return tuple(tuple(o[i:i + 1] for i in range(o.shape[0]))
                 for o in outs)


class Ticket:
    """Handle for one submitted request; ``result()`` forces a flush of
    the owning batcher if the answer is not in yet."""

    __slots__ = ("_batcher", "_value", "_done")

    def __init__(self, batcher: "MicroBatcher"):
        self._batcher = batcher
        self._done = False
        self._value = None

    @property
    def done(self) -> bool:
        return self._done

    def result(self):
        if not self._done:
            self._batcher.flush(reason="result")
        assert self._done, "flush did not resolve this ticket"
        return self._value

    def _resolve(self, value) -> None:
        self._value = value
        self._done = True


class MicroBatcher:
    """Coalesces kNN / range-count / range-list requests per plan
    signature; see the module docstring for the contract.

    ``target`` is what answers the flushed batches: a
    :class:`repro.core.SpatialIndex`, a ``repro.serving.Snapshot``, or
    a zero-arg callable returning either (e.g. ``server.snapshot`` — the
    snapshot is then taken at *flush* time, so one flush answers against
    one consistent version). Reassigning ``target`` drains pending
    requests first: they were submitted against the old target, and
    answering them from a newer version would misattribute results.
    """

    def __init__(self, target=None, *, max_batch: int = 1024,
                 max_delay_s: float = 0.002, clock=time.monotonic):
        self._target = target
        self.max_batch = int(max_batch)
        self.max_delay_s = float(max_delay_s)
        self._clock = clock
        self._groups: dict[tuple, list] = {}
        self._pending_rows = 0
        self._oldest = None
        self.flushes = 0

    @property
    def target(self):
        return self._target

    @target.setter
    def target(self, value):
        # requests already queued were submitted against the old target;
        # answering them from a newer version would silently break the
        # snapshot attribution, so drain first
        if self._pending_rows and value is not self._target:
            self.flush(reason="retarget")
        self._target = value

    # -- submission --------------------------------------------------------

    def submit_knn(self, qpts, k: int, *, impl: str = "auto") -> Ticket:
        """Queue a kNN request (1 or more query points); the ticket
        resolves to the same ``(d2, ids)`` the request would get from
        ``index.knn(qpts, k, impl=impl)``."""
        qpts = _as_rows(qpts)
        key = ("knn", int(k), qpts.shape[1], str(qpts.dtype), impl)
        return self._enqueue(key, (qpts,), qpts.shape[0])

    def submit_range_count(self, lo, hi) -> Ticket:
        """Queue a range-count request (1 or more boxes)."""
        lo, hi = _as_rows(lo), _as_rows(hi)
        key = ("range_count", lo.shape[1], str(lo.dtype))
        return self._enqueue(key, (lo, hi), lo.shape[0])

    def submit_range_list(self, lo, hi) -> Ticket:
        """Queue a range-list request; resolves to ``(ids, counts)``."""
        lo, hi = _as_rows(lo), _as_rows(hi)
        key = ("range_list", lo.shape[1], str(lo.dtype))
        return self._enqueue(key, (lo, hi), lo.shape[0])

    def _enqueue(self, key: tuple, arrays: tuple, rows: int) -> Ticket:
        t = Ticket(self)
        now = self._clock()
        self._groups.setdefault(key, []).append((t, arrays, rows, now))
        self._pending_rows += rows
        if self._oldest is None:
            self._oldest = now
        if self._pending_rows >= self.max_batch:
            self.flush(reason="size")
        elif now - self._oldest >= self.max_delay_s:
            self.flush(reason="deadline")
        return t

    @property
    def pending(self) -> int:
        """Queued request rows not yet flushed."""
        return self._pending_rows

    def poll(self) -> int:
        """Flush if the oldest queued request has exceeded the delay
        deadline (for trickle-traffic wait loops that watch
        ``Ticket.done`` instead of calling ``result()``); returns the
        number of engine calls issued."""
        if (self._oldest is not None
                and self._clock() - self._oldest >= self.max_delay_s):
            return self.flush(reason="deadline")
        return 0

    # -- execution ---------------------------------------------------------

    def _resolve_target(self):
        t = self.target() if callable(self.target) else self.target
        if t is None:
            raise ValueError("MicroBatcher.target is not set")
        return t

    def flush(self, *, reason: str = "explicit") -> int:
        """Execute every pending group as one pow2-padded batch each;
        returns the number of batched engine calls issued. ``reason``
        (size | deadline | result | retarget | explicit) is recorded on
        the ``batcher.flush.<reason>`` obs counter."""
        groups, self._groups = self._groups, {}
        self._pending_rows, self._oldest = 0, None
        if not groups:
            return 0
        obs.count(f"batcher.flush.{reason}")
        target = self._resolve_target()
        now = self._clock()
        calls = 0
        for key, reqs in groups.items():
            self._run_group(target, key, reqs, now)
            calls += 1
        self.flushes += calls
        return calls

    def _run_group(self, target, key: tuple, reqs: list, now) -> None:
        op = key[0]
        q = sum(r[2] for r in reqs)
        obs.observe("batcher.coalesce_rows", q)
        obs.observe("batcher.pad_rows", _pow2(q) - q)
        for _, _, _, ts in reqs:
            obs.observe("batcher.wait_s", now - ts)
        with obs.span("batcher.flush", op=op, rows=q, reqs=len(reqs)):
            with obs.span("batcher.pack"):
                cols = [jnp.asarray(_concat_pad([r[1][i] for r in reqs], q))
                        for i in range(len(reqs[0][1]))]
            with obs.span("batcher.call"):
                if op == "knn":
                    # local indexes answer (d2, ids); distributed
                    # snapshots answer (d2, points, valid) — split
                    # whatever came back
                    outs = tuple(target.knn(cols[0], key[1], impl=key[4]))
                elif op == "range_count":
                    outs = (target.range_count(cols[0], cols[1]),)
                else:
                    ids, cnt = target.range_list(cols[0], cols[1])
                    outs = (ids, cnt)
            with obs.span("batcher.split"):
                if (all(r[2] == 1 for r in reqs)
                        and all(isinstance(o, jax.Array) for o in outs)):
                    obs.count("batcher.split.fused")
                    # request i takes row i of every output; pad rows go unused
                    answers = zip(*_split_rows(*outs))
                else:
                    obs.count("batcher.split.sliced")
                    answers, start = [], 0
                    for _, _, rows, _ts in reqs:
                        answers.append(tuple(o[start: start + rows]
                                             for o in outs))
                        start += rows
                for (ticket, *_), sl in zip(reqs, answers):
                    ticket._resolve(sl if len(sl) > 1 else sl[0])
