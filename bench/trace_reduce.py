"""Reduce a profiler trace of the window to the numbers the readers use.

The trace (``.xplane.pb``, read with JAX's own ``ProfileData``) is first
brought to a neutral form, ``Events``:

* ``modules``: each program run on a device, from the device plane's
  ``XLA Modules`` line: ``(device, jit name, start_ns, dur_ns, run_id)``;
* ``ops``: each op, from the ``XLA Ops`` line: ``(device, short name,
  start_ns, dur_ns, is_kernel)``, where a Mosaic kernel is an op whose
  HLO calls ``custom_call_target="tpu_custom_call"``;
* ``launches``: each program the host enqueued, in order, from the
  ``PJRT_LoadedExecutable_Execute`` events: ``(start_ns, jit name)``;
* ``spans``: the harness's own spans (``bench.*``, written with
  ``jax.profiler.TraceAnnotation``): ``(name, start_ns, dur_ns)``.

A device runs its programs in the order they were enqueued, so the i-th
launch is the i-th module by ``run_id``; the harness span open at a
launch says which part of the loop enqueued the program. From that,
``Reduced`` gives:

* ``busy_s``: the union of op intervals inside the ``bench.window``
  span, per device, averaged over the devices used; ``window_s``;
* each module's role: ``knn`` where it runs the Mosaic kernel;
  ``update`` for the program's jit-cached closures (``jit_run``)
  launched while the harness dispatched updates (``bench.dispatch``,
  ``bench.update``); ``range`` for those launched from a batcher flush
  (``bench.submit``, ``bench.poll``) without a kernel; ``other``
  otherwise;
* device seconds per role, and of the kernel's ops;
* device 0's idle gaps, by the innermost harness span open at each
  gap's middle (``host.other`` where none is).

Where launches and modules cannot be matched (their counts or jit names
differ), no module gets a role but ``knn``, and readers that need one
read nothing: a missing name is reported as absent, never as zero.
``tests/bench/test_bench_trace.py`` pins this on a recorded trace.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import shutil
from collections import defaultdict
from dataclasses import dataclass, field

KERNEL = 'custom_call_target="tpu_custom_call"'
LAUNCH = "PJRT_LoadedExecutable_Execute"
PROGRAM = "jit_run"            # the engine's and the index's closures
UPDATE_SPANS = ("bench.dispatch", "bench.update")
FLUSH_SPANS = ("bench.submit", "bench.poll")
WINDOW = "bench.window"
_OP = re.compile(r"%\S+ = (.*?) ([a-z][\w-]*)\(")


@dataclass
class Events:
    modules: list = field(default_factory=list)
    ops: list = field(default_factory=list)
    launches: list = field(default_factory=list)
    spans: list = field(default_factory=list)

    def to_json(self) -> dict:
        return {"modules": self.modules, "ops": self.ops,
                "launches": self.launches, "spans": self.spans}

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        return cls(*([tuple(x) for x in d[k]] for k in
                     ("modules", "ops", "launches", "spans")))


def short_op(hlo: str) -> str:
    """``<op kind> <result type>`` from an op's HLO text."""
    m = _OP.match(hlo)
    if not m:
        return hlo[:60]
    kind = "tpu_custom_call" if KERNEL in hlo else m.group(2)
    return f"{kind} {m.group(1)[:48]}"


def events_from_xplane(path: str) -> Events:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ev = Events()
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        run = dict(e.stats).get("run_id", -1)
                        ev.modules.append((dev, e.name.split("(")[0],
                                           e.start_ns, e.duration_ns,
                                           int(run)))
                elif line.name == "XLA Ops":
                    for e in line.events:
                        ev.ops.append((dev, short_op(e.name), e.start_ns,
                                       e.duration_ns, KERNEL in e.name))
        elif plane.name.startswith("/host:"):
            # the thread that runs Python: JAX's dispatch (PjitFunction)
            # and its launches, and the harness's spans, are on its line
            for line in plane.lines:
                jit, launches = [], []
                for e in line.events:
                    if e.name.startswith("bench."):
                        ev.spans.append((e.name, e.start_ns, e.duration_ns))
                    elif e.name.startswith("PjitFunction("):
                        jit.append((e.start_ns, e.start_ns + e.duration_ns,
                                    "jit_" + e.name[13:-1]))
                    elif e.name.startswith(LAUNCH):
                        launches.append(e.start_ns)
                if jit:
                    ev.launches += [(t, _innermost(jit, t))
                                    for t in sorted(launches)]
    ev.launches.sort()
    return ev


def _innermost(intervals, t):
    best = None
    for s, e, name in intervals:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else None


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Reduced:
    """What the readers read from one traced window."""

    def __init__(self, ev: Events, chips: int = 1):
        win = [s for s in ev.spans if s[0] == WINDOW]
        ends = [(m[2], m[2] + m[3]) for m in ev.modules]
        self.w0 = win[0][1] if win else min(s for s, _ in ends)
        self.w1 = win[0][1] + win[0][2] if win else max(e for _, e in ends)
        self.window_s = (self.w1 - self.w0) / 1e9
        self.spans = sorted((s for s in ev.spans if s[0] != WINDOW),
                            key=lambda s: s[1])
        w0, w1 = self.w0, self.w1
        self.ops = [o for o in ev.ops if o[2] < w1 and o[2] + o[3] > w0]
        by_dev = defaultdict(list)
        for o in self.ops:
            by_dev[o[0]].append((max(o[2], w0), min(o[2] + o[3], w1)))
        self.busy = {d: _union(iv) for d, iv in by_dev.items()}
        used = sorted(self.busy)[:chips] or [0]
        self.busy_s = sum(sum(e - s for s, e in self.busy.get(d, []))
                          for d in used) / len(used) / 1e9
        self._roles(ev)

    def _roles(self, ev: Events):
        mods = sorted((m for m in ev.modules if m[0] == 0),
                      key=lambda m: m[4])
        # matched where the program's closures sit at the same places
        # in both orders (small helper programs JAX launches from
        # inside one another may trade places)
        self.matched = (len(mods) == len(ev.launches) and all(
            (m[1] == PROGRAM) == (name == PROGRAM)
            for m, (_, name) in zip(mods, ev.launches)))
        kernel_at = sorted((o[2], o[3]) for o in ev.ops
                           if o[0] == 0 and o[4])
        self.modules = []       # (role, start, dur, kernel_ns)
        for i, m in enumerate(mods):
            s0, s1 = m[2], m[2] + m[3]
            if not (s0 < self.w1 and s1 > self.w0):
                continue
            k = sum(d for s, d in kernel_at if s0 <= s < s1)
            if k:
                role = "knn"
            elif self.matched and m[1] == PROGRAM:
                span = self.span_at(ev.launches[i][0])
                role = ("update" if span in UPDATE_SPANS else
                        "range" if span in FLUSH_SPANS else "other")
            else:
                role = "other"
            self.modules.append((role, s0, m[3], k))

    def span_at(self, t):
        """The innermost harness span open at ``t``, or None."""
        best = None
        for name, s0, dur in self.spans:
            if s0 > t:
                break
            if s0 + dur >= t and (best is None or dur < best[1]):
                best = (name, dur)
        return best[0] if best else None

    # -- what the readers ask -------------------------------------------

    def device_s(self, role: str, kernel: bool | None = None):
        """Device seconds of the programs with ``role``: whole programs,
        or with ``kernel`` True the Mosaic kernel's ops only, False all
        but those. ``None`` where no program has that role."""
        mods = [m for m in self.modules if m[0] == role]
        if not mods:
            return None
        if kernel is None:
            return sum(m[2] for m in mods) / 1e9
        k = sum(m[3] for m in mods)
        return (k if kernel else sum(m[2] for m in mods) - k) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def idle_gaps(self, device: int = 0):
        """Seconds of device idle time in the window, by the innermost
        harness span open at each gap's middle."""
        gaps, t = [], self.w0
        for s, e in self.busy.get(device, []):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.w1:
            gaps.append((t, self.w1))
        out = defaultdict(float)
        for g0, g1 in gaps:
            out[self.span_at((g0 + g1) / 2) or "host.other"] += \
                (g1 - g0) / 1e9
        return sorted(out.items(), key=lambda kv: -kv[1])

    def top_ops(self, n: int = 10):
        """Device seconds by innermost op (an op that holds others, such
        as a ``while``, is left out), named ``<role>:<op>``."""
        ops = sorted((o for o in self.ops if o[0] == 0),
                     key=lambda o: (o[2], -o[3]))
        mods = sorted((m[1], m[1] + m[2], m[0]) for m in self.modules)
        tot = defaultdict(float)
        j = 0
        for i, o in enumerate(ops):
            if i + 1 < len(ops) and ops[i + 1][2] < o[2] + o[3]:
                continue            # holds the next op
            while j < len(mods) and mods[j][1] <= o[2]:
                j += 1
            role = mods[j][2] if j < len(mods) and mods[j][0] <= o[2] \
                else "other"
            tot[f"{role}:{o[1]}"] += o[3] / 1e9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]

    def breakdown(self) -> dict:
        return {"device_ops": [list(x) for x in self.top_ops()],
                "idle_gaps": [list(x) for x in self.idle_gaps()[:10]]}

    def summary(self) -> dict:
        by_role = defaultdict(float)
        count = defaultdict(int)
        for m in self.modules:
            by_role[m[0]] += m[2] / 1e9
            count[m[0]] += 1
        return {"window_s": self.window_s, "busy_s": self.busy_s,
                "launches_matched": self.matched,
                "programs_by_role": dict(count),
                "device_s_by_role": dict(by_role)}


def find_xplane(tdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    return paths[-1]


def reduce_dir(tdir: str, chips: int = 1) -> Reduced:
    return Reduced(events_from_xplane(find_xplane(tdir)), chips=chips)


def remove(tdir: str) -> None:
    shutil.rmtree(tdir, ignore_errors=True)


def save_events(ev: Events, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(ev.to_json(), f)


def load_events(path: str) -> Events:
    with gzip.open(path, "rt") as f:
        return Events.from_json(json.load(f))
