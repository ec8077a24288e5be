"""The program's own spans in a profiler trace, and what they measure.

With ``repro.obs.Recorder(annotate=True)`` installed while the JAX
profiler traces, every ``obs.span`` of the program is also a
``jax.profiler.TraceAnnotation``: it lands on the host thread's line of
the ``.xplane.pb``, on the clock of the device ops. This module
collects those spans (names under ``batcher.``, ``engine.`` and
``serving.``, as ``(name, start_ns, dur_ns)``) beside the neutral
``Events`` of ``bench/trace_reduce.py``, and reduces them to:

* ``flush_split_ms``: ``batcher.split`` time (slicing each ticket's
  answer out of a flushed batch) per ``batcher.flush`` span;
* ``range_sync_ms``: ``engine.range_count.sync`` time (the host's wait
  on the truncation read) per ``engine.range_count`` call;
* ``commit_host_ms``: per ``serving.commit``, its time less its
  ``serving.commit.wait`` child: the host work of a commit;
* ``commit_late_ms``: per commit, the end of ``serving.commit.wait``
  less the end of the last device op of the update programs launched
  since the previous commit (or less the wait's start, where the wait
  began after that op), floored at 0: how late the host woke;
* ``idle_gaps``: device 0's idle time by the innermost span open at
  each gap's middle, harness or program span (``host.other`` where
  none is);
* ``roles``: each program's role from the program span open at its
  launch (``engine.knn``, ``engine.range_count``, ``serving.insert`` /
  ``serving.delete``), to set beside the role the harness span gives.

Each counts spans that start inside the ``bench.window`` span, and
reads ``None`` where the trace holds none of the spans it needs (a
program without them, or a run without the recorder): never 0.

The harness does not call this module yet: ``bench/run.py`` installs no
recorder and ``bench/trace_reduce.py`` keeps only ``bench.*`` spans.
``tests/bench/test_bench_program_spans.py`` pins it on traces recorded
on a TPU v5e with the recorder installed.
"""

from __future__ import annotations

import bisect
import copy
import gzip
import json

from bench import trace_reduce as tr

PREFIXES = ("batcher.", "engine.", "serving.")
ROLES = {"engine.knn": "knn", "engine.range_count": "range",
         "serving.insert": "update", "serving.delete": "update"}


def spans_from_xplane(path: str) -> list:
    """Program spans on the host planes, in start order."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.duration_ns)
                        for e in line.events
                        if e.name.startswith(PREFIXES)]
    return sorted(out, key=lambda s: s[1])


def save(ev: tr.Events, spans: list, path: str) -> None:
    """``Events`` with a ``program_spans`` field, gzip JSON: the old
    ``trace_reduce.load_events`` still reads the file."""
    with gzip.open(path, "wt") as f:
        json.dump({**ev.to_json(), "program_spans": spans}, f)


def load(path: str):
    """``(Events, program spans)``; the spans are ``[]`` for a file
    written before they existed."""
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    return (tr.Events.from_json(d),
            [tuple(s) for s in d.get("program_spans", [])])


def _end(s) -> int:
    return s[1] + s[2]


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and _end(child) <= _end(parent)


class ProgramSpans:
    """The program spans of one traced window, reduced."""

    def __init__(self, ev: tr.Events, spans: list, chips: int = 1):
        self.ev = ev
        self.red = tr.Reduced(ev, chips=chips)
        w0, w1 = self.red.w0, self.red.w1
        self.spans = [s for s in spans if w0 <= s[1] < w1]

    def named(self, name: str) -> list:
        return [s for s in self.spans if s[0] == name]

    def _per(self, name: str, per: str):
        """Milliseconds of ``name`` spans per ``per`` span, or None."""
        n = len(self.named(per))
        if not n or not self.named(name):
            return None
        return sum(s[2] for s in self.named(name)) / 1e6 / n

    def flush_split_ms(self):
        return self._per("batcher.split", "batcher.flush")

    def range_sync_ms(self):
        return self._per("engine.range_count.sync", "engine.range_count")

    def _commits(self):
        """(commit span, its wait child) per commit that has one."""
        waits = self.named("serving.commit.wait")
        out = []
        for c in self.named("serving.commit"):
            w = [x for x in waits if _inside(x, c)]
            if w:
                out.append((c, w[0]))
        return out

    def commit_host_ms(self):
        commits = self._commits()
        if not commits:
            return None
        return sum(c[2] - w[2] for c, w in commits) / 1e6 / len(commits)

    def _launched(self):
        """(launch ns, module) of device 0's programs, paired in order
        where ``trace_reduce`` pairs them, else []."""
        if not self.red.matched:
            return []
        mods = sorted((m for m in self.ev.modules if m[0] == 0),
                      key=lambda m: m[4])
        return [(t, m) for (t, _), m in zip(self.ev.launches, mods)]

    def commit_steps(self) -> list:
        """Per commit that waited on update programs: ``(commit, wait,
        late_ns)``, where ``late_ns`` is the end of the wait less the
        later of the wait's start and the end of the last device op of
        the update programs launched since the previous commit."""
        launched = self._launched()
        updates = [s for s in self.spans if ROLES.get(s[0]) == "update"]
        ops = sorted(o[2] + o[3] for o in self.ev.ops if o[0] == 0)
        out, prev = [], None
        for c, w in self._commits():
            step = [u for u in updates if u[1] < c[1]
                    and (prev is None or u[1] >= _end(prev))]
            prev = c
            mods = [m for t, m in launched
                    if any(u[1] <= t <= _end(u) for u in step)]
            if mods:
                last = max(_end_of_last_op(ops, m) for m in mods)
                out.append((c, w, max(0, _end(w) - max(last, w[1]))))
        return out

    def commit_late_ms(self):
        lates = [late for _, _, late in self.commit_steps()]
        return sum(lates) / 1e6 / len(lates) if lates else None

    def _attributed(self, spans: list) -> tr.Reduced:
        """The reduced window with ``spans`` in place of the harness's,
        so that ``trace_reduce``'s own innermost-span rule applies."""
        red = copy.copy(self.red)
        red.spans = sorted(spans, key=lambda s: s[1])
        return red

    def idle_gaps(self, device: int = 0):
        """Seconds of device idle time in the window by the innermost
        harness or program span open at each gap's middle."""
        return self._attributed(self.red.spans + self.spans).idle_gaps(
            device)

    def roles(self) -> dict:
        """Module start (ns) -> role from the program span open at its
        launch (``None`` for other spans), for launched programs that
        run in the window."""
        red = self._attributed(self.spans)
        return {m[2]: ROLES.get(red.span_at(t)) for t, m in self._launched()
                if m[2] < red.w1 and m[2] + m[3] > red.w0}


def _end_of_last_op(op_ends: list, module) -> int:
    """End of the last device op inside ``module``'s interval (the
    module's own end where the trace holds none)."""
    s0, s1 = module[2], module[2] + module[3]
    i = bisect.bisect_right(op_ends, s1) - 1
    return op_ends[i] if i >= 0 and op_ends[i] > s0 else s1
