"""Recorder core: spans, counters, gauges, pow2 histograms, and the
deferred device-read list.

Everything here is host-side Python over stdlib types — the subsystem
is zero-dependency by design (``jax`` is touched only inside
:meth:`Recorder.resolve`, the one sanctioned sync point, and by the
profiler sink of ``Recorder(annotate=True)``, which only writes host
annotations) so enabling it can never change what the instrumented code
compiles or dispatches.

Two invariants this module owns (see ROADMAP "Observability"):

* **No host sync off the barrier.** Instrumented code may *attach*
  in-flight device values to a span (:meth:`Span.defer`), to a named
  counter (:meth:`Recorder.add_deferred`) or to a gauge
  (:meth:`Recorder.add_deferred_gauge`) — all list appends. The
  host read happens only in :meth:`Recorder.resolve`, which callers
  invoke at an existing barrier (``SpatialServer.commit``, report
  time). This mirrors the serving runtime's sticky-``overflowed``
  pattern: the flag rides along on device and one read at the sync
  point covers everything since. The ``obs-deferred-sync`` lint rule
  enforces it over this package.
* **Disabled mode is near-free.** The module-level helpers in
  :mod:`repro.obs` check one dict slot and return a shared no-op span
  when no recorder is installed; nothing is allocated and no clock is
  read (asserted by the overhead microtest in tests/test_obs.py).

Histograms bucket observations by power of two (bucket key = the
smallest ``2**e`` >= value, 0 for 0) — the same pow2 shape the engine's
buffer escalation and the batcher's padding already quantize to — and
additionally retain up to ``max_samples`` raw samples so report-time
percentiles (p50/p95/p99) are exact for bounded runs like the workload
driver's.
"""

from __future__ import annotations

import math
import threading
import time


def pow2_bucket(value) -> float:
    """Upper edge of the power-of-two bucket holding ``value``:
    smallest ``2.0**e`` >= value (0.0 for values <= 0)."""
    v = float(value)
    if v <= 0.0:
        return 0.0
    m, e = math.frexp(v)          # v = m * 2**e, 0.5 <= m < 1
    return float(2.0 ** (e - 1 if m == 0.5 else e))


def percentile(sorted_samples, p: float) -> float:
    """Nearest-rank percentile over an already-sorted list."""
    n = len(sorted_samples)
    if not n:
        return 0.0
    rank = min(n - 1, max(0, math.ceil(p / 100.0 * n) - 1))
    return float(sorted_samples[rank])


class Hist:
    """Pow2-bucket histogram with bounded raw-sample retention."""

    __slots__ = ("buckets", "samples", "count", "total", "min", "max",
                 "max_samples", "dropped")

    def __init__(self, max_samples: int = 8192):
        self.buckets: dict[float, int] = {}
        self.samples: list[float] = []
        self.count = 0
        self.total = 0.0
        self.min = math.inf
        self.max = -math.inf
        self.max_samples = max_samples
        self.dropped = 0              # samples past retention (buckets
                                      # still count them)

    def observe(self, value) -> None:
        v = float(value)
        b = pow2_bucket(v)
        self.buckets[b] = self.buckets.get(b, 0) + 1
        self.count += 1
        self.total += v
        self.min = min(self.min, v)
        self.max = max(self.max, v)
        if len(self.samples) < self.max_samples:
            self.samples.append(v)
        else:
            self.dropped += 1

    def summary(self, scale: float = 1.0) -> dict:
        """count/mean/min/max plus p50/p95/p99 — exact from retained
        samples, falling back to bucket upper edges past retention."""
        if not self.count:
            return {"count": 0}
        out = {"count": self.count,
               "mean": scale * self.total / self.count,
               "min": scale * self.min, "max": scale * self.max}
        if self.dropped:
            # percentile from bucket edges (upper bounds -> pessimistic)
            edges = sorted(self.buckets)
            cum, spread = 0, []
            for e in edges:
                spread.extend([e] * self.buckets[e])
            samples = spread
        else:
            samples = sorted(self.samples)
        for p in (50.0, 95.0, 99.0):
            out[f"p{p:g}"] = scale * percentile(samples, p)
        return out

    def to_dict(self) -> dict:
        return {"buckets": {repr(k): v
                            for k, v in sorted(self.buckets.items())},
                **self.summary()}


class Span:
    """One timed section. Use as a context manager (the common path) or
    drive ``begin()``/``end()`` by hand. ``set()`` adds attributes;
    ``defer()`` attaches an in-flight device value whose host read is
    postponed to the owning recorder's :meth:`Recorder.resolve`.

    ``parent`` is the name of the span that was open on the same thread
    when this one began (None at the top). With ``Recorder(annotate=
    True)`` the span also enters a ``jax.profiler.TraceAnnotation`` of
    its name, so it lands in a profiler trace on the device ops' clock."""

    __slots__ = ("rec", "name", "cat", "args", "t0", "dur", "parent",
                 "_ann")

    def __init__(self, rec: "Recorder", name: str, cat: str, args: dict):
        self.rec = rec
        self.name = name
        self.cat = cat
        self.args = args
        self.t0 = None
        self.dur = None
        self.parent = None
        self._ann = None

    def __enter__(self) -> "Span":
        stack = self.rec._stack()
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        if self.rec.annotate:
            self._ann = self.rec._annotation(self.name)
            self._ann.__enter__()
        self.t0 = self.rec.clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.end()
        return False

    def begin(self) -> "Span":
        return self.__enter__()

    def end(self) -> None:
        self.dur = self.rec.clock() - self.t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        stack = self.rec._stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:               # ended out of order, by hand
            stack.remove(self)
        self.rec._finish(self)

    def set(self, **attrs) -> "Span":
        self.args.update(attrs)
        return self

    def defer(self, key: str, value) -> "Span":
        """Attach an in-flight device value; ``args[key]`` is filled in
        (plus ``<key>_resolved_s``, the barrier-side completion stamp)
        at the recorder's next ``resolve()``. Never reads the value."""
        # placeholder keeps args non-empty so _finish retains the dict
        # (resolve() mutates it in place after the span has ended)
        self.args[key] = None
        with self.rec._lock:
            self.rec._pending.append((self.args, key, value))
        return self

    @property
    def done(self) -> bool:
        return self.dur is not None


class NullSpan:
    """Shared no-op stand-in returned while obs is disabled: every
    method is a cheap self-return, so instrumentation sites cost one
    dict lookup and an attribute call."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def begin(self):
        return self

    def end(self):
        return None

    def set(self, **attrs):
        return self

    def defer(self, key, value):
        return self

    done = True


NULL_SPAN = NullSpan()


class Recorder:
    """Collects spans/counters/gauges/histograms for one run.

    Host-side only: ``clock`` is a monotonic timer (``perf_counter``),
    events are plain dicts, and the only device interaction is the
    deferred-read list drained by :meth:`resolve` at a barrier.

    Thread-safe: the batcher's worker threads and the main thread mutate
    counters/hists concurrently, so every read-modify-write goes through
    one uncontended lock (the disabled path in :mod:`repro.obs` never
    reaches it).

    Opt-in extras (both default off, both drained at barriers only):

    * ``memory_snapshots`` — each :meth:`resolve` also records backend
      allocator gauges (``backend.mem.d<id>.*``) from
      ``device.memory_stats()``; that call is a device-runtime read, so
      it is allowed *only* lexically inside ``resolve`` (lint rule
      ``obs-deferred-sync``).
    * ``capture_costs`` — :mod:`repro.obs.costs` AOT-compiles each new
      query/update plan once and records ``plan.cost.*`` counters;
      ``_cost_sigs`` tracks which plan signatures were already captured.

    ``annotate`` (default off) is the profiler sink: every span also
    enters and exits a ``jax.profiler.TraceAnnotation`` under its own
    name, so a JAX profiler trace taken meanwhile holds the program's
    spans on the host thread's line, on the same clock as the device
    ops. It writes annotations only; it reads nothing from the device.
    """

    def __init__(self, clock=time.perf_counter, max_samples: int = 8192,
                 keep_events: bool = True, capture_costs: bool = False,
                 memory_snapshots: bool = False, annotate: bool = False):
        self.clock = clock
        self.keep_events = keep_events
        self.max_samples = max_samples
        self.capture_costs = capture_costs
        self.memory_snapshots = memory_snapshots
        self.annotate = annotate
        if annotate:
            # deferred import: obs stays importable with the stdlib alone
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
        self._local = threading.local()    # per-thread open-span stack
        self.events: list[dict] = []       # completed spans, in order
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, dict] = {}  # name -> {value, max, n}
        self.hists: dict[str, Hist] = {}
        self._pending: list[tuple[dict | str, str | None, object]] = []
        self._cost_sigs: set[str] = set()  # plan sigs already captured
        self._lock = threading.Lock()
        self.t0 = self.clock()

    # -- spans -------------------------------------------------------------

    def span(self, name: str, cat: str = "", **attrs) -> Span:
        return Span(self, name, cat, attrs)

    def _stack(self) -> list:
        """The calling thread's open spans, innermost last."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _finish(self, span: Span) -> None:
        if self.keep_events:
            ev = {"name": span.name, "ts": span.t0 - self.t0,
                  "dur": span.dur}
            if span.cat:
                ev["cat"] = span.cat
            if span.parent is not None:
                ev["parent"] = span.parent
            if span.args:
                ev["args"] = span.args
            with self._lock:
                self.events.append(ev)

    def add_span(self, name: str, start_s: float, dur_s: float,
                 cat: str = "", **attrs) -> None:
        """Record an externally-timed section on the timeline
        (``start_s`` in this recorder's clock base)."""
        if self.keep_events:
            ev = {"name": name, "ts": start_s - self.t0, "dur": dur_s}
            if cat:
                ev["cat"] = cat
            if attrs:
                ev["args"] = attrs
            with self._lock:
                self.events.append(ev)

    # -- metrics -----------------------------------------------------------

    def count(self, name: str, n: float = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def gauge(self, name: str, value) -> None:
        with self._lock:
            g = self.gauges.get(name)
            if g is None:
                self.gauges[name] = {"value": value, "max": value, "n": 1}
            else:
                g["value"] = value
                if value > g["max"]:
                    g["max"] = value
                g["n"] += 1

    def observe(self, name: str, value) -> None:
        with self._lock:
            h = self.hists.get(name)
            if h is None:
                h = self.hists[name] = Hist(self.max_samples)
            h.observe(value)

    def hist(self, name: str) -> Hist | None:
        return self.hists.get(name)

    def drop(self, prefix: str) -> None:
        """Forget histograms under a name prefix (e.g. a latency
        recorder resetting its measured window after warmup)."""
        with self._lock:
            for name in [n for n in self.hists if n.startswith(prefix)]:
                del self.hists[name]

    # -- deferred device reads (resolve at barriers only) ------------------

    def add_deferred(self, name: str, value) -> None:
        """Attach an in-flight device scalar to counter ``name``; it is
        folded in (via one host read) at the next ``resolve()``."""
        with self._lock:
            self._pending.append((name, None, value))

    def add_deferred_gauge(self, name: str, value) -> None:
        """Attach an in-flight device scalar to gauge ``name``; it is
        set (via one host read) at the next ``resolve()``."""
        with self._lock:
            self._pending.append((name, "gauge", value))

    @property
    def pending(self) -> int:
        """Deferred device reads not yet resolved."""
        return len(self._pending)

    def resolve(self) -> int:
        """THE sync point: drain the deferred list with one blocking
        host read per entry. Call only from an existing barrier
        (``commit()``, report time) — everywhere else obs must stay
        sync-free (lint rule ``obs-deferred-sync``).

        With ``memory_snapshots`` on, also records backend allocator
        gauges here — ``device.memory_stats()`` is a device-runtime
        read, so this is the only place in the package allowed to call
        it (the extended ``obs-deferred-sync`` rule checks that
        lexically)."""
        with self._lock:
            pending, self._pending = self._pending, []
        if self.memory_snapshots:
            import jax  # deferred import: obs stays stdlib-importable
            for dev in jax.local_devices():
                try:
                    stats = dev.memory_stats()
                except Exception:      # backend without allocator stats
                    stats = None
                if not stats:          # CPU devices report None
                    continue
                used = stats.get("bytes_in_use")
                if used is not None:
                    self.gauge(f"backend.mem.d{dev.id}.bytes_in_use",
                               int(used))
                peak = stats.get("peak_bytes_in_use")
                if peak is not None:
                    self.gauge(f"backend.mem.d{dev.id}.peak_bytes",
                               int(peak))
        if not pending:
            return 0
        import jax  # deferred import: obs stays importable stdlib-only
        for target, key, value in pending:
            value = jax.block_until_ready(value)
            now = self.clock() - self.t0
            if isinstance(target, str) and key == "gauge":
                self.gauge(target, float(value))  # deferred gauge
            elif isinstance(target, str):         # deferred counter
                self.count(target, float(value))
            else:                                 # span attribute
                try:
                    target[key] = float(value)
                except (TypeError, ValueError):   # non-scalar payload
                    target[key] = True
                target[f"{key}_resolved_s"] = now
        return len(pending)

    # -- reporting ---------------------------------------------------------

    def report(self) -> dict:
        """Resolve pending reads and reduce everything to one json-able
        payload (the shape the exporters and the view CLI consume)."""
        self.resolve()
        return {
            "wall_s": self.clock() - self.t0,
            "counters": dict(sorted(self.counters.items())),
            "gauges": {k: dict(v)
                       for k, v in sorted(self.gauges.items())},
            "hists": {k: v.to_dict()
                      for k, v in sorted(self.hists.items())},
            "spans": self.span_summary(),
        }

    def span_summary(self) -> dict:
        """Per-name span stats (count, total/mean/p50/p95/p99 ms)."""
        by_name: dict[str, Hist] = {}
        for ev in self.events:
            h = by_name.get(ev["name"])
            if h is None:
                h = by_name[ev["name"]] = Hist(self.max_samples)
            h.observe(ev["dur"])
        out = {}
        for name, h in sorted(by_name.items()):
            s = h.summary(scale=1e3)           # ms
            s["total_ms"] = h.total * 1e3
            out[name] = s
        return out
