"""Exporter: the Chrome trace-event format.

Derived from :meth:`Recorder.report` / ``Recorder.events`` (so exporting
resolves deferred device reads — it is a report barrier):
:func:`write_chrome_trace` writes the ``{"traceEvents": [...]}`` JSON
that chrome://tracing and Perfetto (https://ui.perfetto.dev) load
directly. Spans become complete ("ph": "X") events with microsecond
timestamps, each with its enclosing span's name as ``args.parent``;
counters, gauges and histogram summaries ride in ``otherData`` so the
summary CLI (:mod:`repro.obs.view`) can reconstruct the full report
from the trace file alone.

The other format in use is the JAX profiler's own trace, which holds
the spans of a ``Recorder(annotate=True)`` beside the device ops.
"""

from __future__ import annotations

import json
import os

from .record import Recorder

TRACE_VERSION = 1


def _ensure_dir(path: str) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)


def chrome_trace(rec: Recorder, pid: int = 1, tid: int = 1) -> dict:
    report = rec.report()                     # resolves deferred reads
    events = []
    for ev in rec.events:
        out = {"name": ev["name"], "ph": "X", "pid": pid, "tid": tid,
               "ts": ev["ts"] * 1e6, "dur": ev["dur"] * 1e6,
               "cat": ev.get("cat", "obs")}
        args = dict(ev.get("args", {}))
        if "parent" in ev:
            args["parent"] = ev["parent"]
        if args:
            out["args"] = args
        events.append(out)
    # counters as Chrome counter ("C") samples at end-of-run so the
    # totals are visible on the timeline too
    t_end = report["wall_s"] * 1e6
    for name, value in report["counters"].items():
        events.append({"name": name, "ph": "C", "pid": pid, "ts": t_end,
                       "args": {"value": value}})
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {"version": TRACE_VERSION,
                      "wall_s": report["wall_s"],
                      "counters": report["counters"],
                      "gauges": report["gauges"],
                      "hists": report["hists"],
                      "spans": report["spans"]},
    }


def write_chrome_trace(rec: Recorder, path: str) -> str:
    _ensure_dir(path)
    with open(path, "w") as f:
        json.dump(chrome_trace(rec), f, indent=1, sort_keys=True)
    return path
