"""Run one benchmark cell once on the chip and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/``) and a traffic mix (``bench/traffic/``). Set-up
builds the index from the seed on the device and warms up the cell's
shapes; the window then runs the mix's loop for ``--seconds``. With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window (``bench/trace_reduce.py``) and from the harness's own clocks.
Every metric is computed by its reader, ``bench/metrics/<name>.py``.

After the window the timed path's answers are compared with the plain
reference (``bench/reference.py``). The last line on standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (platform, kind, count, ``memory_peak_bytes``; with
``--trace 1`` also ``busy_s`` and ``window_s``), with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit,
which are also the last lines on standard error.

It exits non-zero and prints no result where JAX finds no TPU or fewer
chips than the cell asks for, and where the program is not beside it.
``--control`` (not a benchmark run) also prints the control's readings:
the bfloat16 brute force in the program's place.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# each number compared after the window, and the most it may read:
# every comparison is exact
LIMITS = {"unanswered": 0, "knn_wrong": 0, "range_wrong": 0,
          "live_diff": 0}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also print the control's readings")
    return ap.parse_args(argv)


def say(*parts):
    print(*parts, flush=True)


class CompileCount:
    """Traces and compiles JAX reports, from its monitoring events."""

    def __init__(self):
        import jax
        self.traces = self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def counts(self):
        return self.traces, self.compiles


def _device(jax, chips):
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(len(devs), chips)}


def _peak_bytes(jax, chips) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def main(argv=None, rehearse: dict | None = None):
    """Run the cell. ``rehearse`` (tests only) runs on any platform with
    the given configuration overrides and kNN spelling, and prints no
    result line; the result is returned instead."""
    args = parse(argv)
    from bench import spec
    cell = spec.load(ROOT, args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"bench: no program under {ROOT / 'src'}; nothing was run")
    if rehearse is None:
        # the persistent compile cache lives at a fixed path inside the
        # checkout, so only a cell's first run there compiles
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from bench import loops, trace_reduce
    from repro.configs import platform

    device = _device(jax, cell.chips)
    say(f"device: {json.dumps(device)}")
    if rehearse is None:
        if device["platform"] != "tpu":
            sys.exit(f"bench: JAX found no TPU (platform "
                     f"{device['platform']!r}); nothing was run")
        if len(jax.devices()) < cell.chips:
            sys.exit(f"bench: {cell.name} needs {cell.chips} chips, JAX "
                     f"sees {len(jax.devices())}")
        say(f"compile cache: {platform.use_compile_cache()}")
    cfg = dict(cell.config, **(rehearse or {}).get("config", {}))
    knn_impl = (rehearse or {}).get("knn_impl", "auto")
    compiles = CompileCount()
    Loop = loops.Ingest if cell.mix["loop"] == "closed" else loops.Serve
    ann = loops.Annotations(bool(args.trace))
    say(f"{cell.name}: {cfg['index']} n={cfg['n']:,} seed={args.seed} "
        f"seconds={args.seconds} trace={args.trace}")
    loop = Loop(cfg, cell.mix, args.seed, ann, knn_impl)
    c0 = compiles.counts()
    say(f"set-up: {c0[0]} traces, {c0[1]} compiles (cache misses)")
    tdir = None
    if args.trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    loop.window(args.seconds)
    if args.trace:
        jax.profiler.stop_trace()
    c1 = compiles.counts()
    setup_s = loop.t0 - T_START
    say(f"setup_s {setup_s:.6f}; in the window: {c1[0] - c0[0]} traces, "
        f"{c1[1] - c0[1]} compiles")
    say(f"window: {json.dumps(loop.info())}")
    if rehearse is None:
        device["memory_peak_bytes"] = _peak_bytes(jax, cell.chips)

    run = type("Run", (), {})()
    run.loop, run.setup_s, run.cell, run.trace = loop, setup_s, cell, None
    result_extra = {}
    if args.trace:
        run.trace = trace_reduce.reduce_dir(tdir, chips=cell.chips)
        trace_reduce.remove(tdir)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result_extra["breakdown"] = run.trace.breakdown()
        say(f"trace: {json.dumps(run.trace.summary())}")
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = cell.reader(m["name"])(run)
        if value is None:
            say(f"metric {m['name']}: nothing to read in this run")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    checks = loop.check()
    table = {k: {"value": v, "limit": LIMITS[k]} for k, v in checks.items()}
    correct = all(v <= LIMITS[k] for k, v in checks.items())
    if args.control:
        ctl = loop.check(control=True)
        result_extra["control"] = {
            "correct": all(v <= LIMITS[k] for k, v in ctl.items()),
            "checks": {k: {"value": v, "limit": LIMITS[k]}
                       for k, v in ctl.items()}}
        say(f"control: {json.dumps(result_extra['control'])}")
    result = {"correct": correct, "attempted": loop.attempted(),
              "failed": loop.failed(), "metrics": metrics,
              "device": device, **result_extra, "checks": table}
    for k, v in table.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    if rehearse is not None:
        say(f"rehearsal on {device['platform']}: correct={correct} "
            f"(not a chip run)")
        return result
    say(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
