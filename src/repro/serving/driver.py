"""Workload driver: replay deterministic mixed update/query traces
through the serving runtime and report latency percentiles.

Per (backend, scenario) the driver builds a :class:`SpatialServer`
sized for the trace's peak live points, then replays the trace's steps
in the pipelined serving pattern:

1. take a snapshot of the current head version,
2. dispatch the step's delete + insert (async — versions ``v+1``,
   ``v+2`` go in flight; only the dispatch time is on the critical
   path),
3. answer the step's kNN and range requests **against the pre-step
   snapshot** through the :class:`MicroBatcher` (requests arrive as
   single-query submissions and coalesce into one pow2-padded batch per
   op — their device work overlaps the in-flight updates),
4. ``commit()`` — the only barrier; its wall time is the *exposed*
   update stall, i.e. whatever the queries did not hide.

Recorded ops: ``insert`` / ``delete`` (dispatch latency), ``knn`` /
``range`` (request submit -> result, including device wait) plus their
``_dispatch`` / ``_wait`` segments (host submit+flush time vs device
wait — the split that attributes a round-trip), and ``commit`` (exposed
update stall). Warmup steps run the identical shapes first and are
dropped, so jit compiles and the query engine's pow2 bucket-escalation
retraces never pollute a percentile (the first-timed-batch skew the old
``launch/serve.py`` loop had).

Observability (PR 7): percentiles come from ``repro.obs`` histograms —
install a recorder (or pass ``--obs-trace``) and the same sink collects
the library's own counters/spans (plan-cache traffic, batcher queue
depth/pad waste, commit stalls) and exports a Perfetto-viewable chrome
trace; ``--attributed`` replays one scenario obs-off vs obs-on
side-by-side and writes the attributed kNN round-trip baseline
(``results/serve_trace.json``).

Scenarios are ``repro.data.points.SCENARIOS``: churn over each point
distribution (uniform / sweepline / varden) plus the dynamic shapes
``moving-objects`` and ``sliding-window``.

Run:
  PYTHONPATH=src python -m repro.serving.driver --kinds porth,spac-h
  PYTHONPATH=src python -m repro.serving.driver --smoke
  PYTHONPATH=src python -m repro.serving.driver --json  # results/...
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import numpy as np

from .. import obs
from ..data import points as gen
from .batcher import MicroBatcher
from .metrics import LatencyRecorder
from .server import SpatialServer

DEFAULT_KINDS = ("porth", "spac-h")
DEFAULT_JSON = "results/serve_latency.json"
DEFAULT_OBS_TRACE = "results/obs_trace.json"
DEFAULT_SERVE_TRACE = "results/serve_trace.json"


@dataclasses.dataclass(frozen=True)
class DriverCfg:
    n: int = 20_000           # bootstrap / live-set size
    batch: int = 512          # update batch per step
    steps: int = 6            # measured steps
    warmup: int = 2           # untimed steps (same shapes) dropped
    queries: int = 64         # kNN + range requests per step
    k: int = 10
    box_frac: int = 64        # range boxes span DEFAULT_HI / box_frac
    window: int = 4           # server version window
    # admission knob: high default so flushes are size-triggered (one
    # pow2 shape per op) and a timing-dependent split never compiles a
    # fresh shape inside the measured window; lower it to trade
    # throughput for per-request latency
    max_delay_ms: float = 50.0
    seed: int = 0
    dim: int = 2
    phi: int = 32
    mesh: int = 0             # simulated shard count (0 = single-device)
    # kNN spelling the requests ask for; "auto" is the served route, and
    # "pallas-frontier-interpret" rehearses the kernel path on the CPU
    knn_impl: str = "auto"


def _query_stream(cfg: DriverCfg, scenario: str, step: int):
    """Deterministic per-step query load: kNN points from the scenario's
    distribution (uniform for the dynamic shapes) + range boxes."""
    dist = scenario if scenario in gen.GENERATORS else "uniform"
    key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed + 7), step)
    k1, k2 = jax.random.split(key)
    qpts = gen.GENERATORS[dist](k1, cfg.queries, cfg.dim)
    lo, hi = gen.query_boxes(k2, cfg.queries, cfg.dim,
                             gen.DEFAULT_HI // cfg.box_frac)
    # requests arrive as host-side rows (as they would off the wire);
    # numpy slicing keeps per-submit overhead off the device
    return np.asarray(qpts), np.asarray(lo), np.asarray(hi)


def run_one(kind: str, scenario: str, cfg: DriverCfg,
            verbose: bool = False, mesh=None, return_server: bool = False):
    """Replay one (backend, scenario) trace; returns latency summary +
    sustained throughput for the measured window. With ``mesh`` the
    server's head index is mesh-sharded (``DistributedIndex``) and the
    summary gains a per-shard ``distributed`` section. With
    ``return_server`` the result is ``(summary, server)``, the server
    committed at the trace's last step — for checking what it holds."""
    total = cfg.warmup + cfg.steps
    trace = gen.make_trace(scenario, seed=cfg.seed, n=cfg.n,
                           batch=cfg.batch, steps=total, dim=cfg.dim)
    t0 = time.perf_counter()
    srv = SpatialServer.build(kind, trace.bootstrap, phi=cfg.phi,
                              capacity_points=trace.max_live,
                              window=cfg.window, mesh=mesh)
    jax.block_until_ready(srv.head_index.tree)
    build_s = time.perf_counter() - t0
    batcher = MicroBatcher(max_batch=cfg.queries,
                           max_delay_s=cfg.max_delay_ms / 1e3)
    # share the installed obs recorder (if any) so latency histograms,
    # the library's own counters/spans, and trace export use one sink
    rec = LatencyRecorder(recorder=obs.recorder())
    measured_updates = 0
    for s, step in enumerate(trace.steps):
        if s == cfg.warmup:
            rec.reset()   # drop warmup: compiles + bucket escalations
        snap = srv.snapshot()                       # pre-step version
        batcher.target = snap
        if step.delete is not None:
            with rec.timer("delete", step.delete.shape[0]):
                srv.delete(step.delete)             # async dispatch
        if step.insert is not None:
            with rec.timer("insert", step.insert.shape[0]):
                srv.insert(step.insert)             # async dispatch
        # micro-batched queries against the snapshot: single-query
        # requests coalesce into one pow2-padded engine call per op,
        # overlapping the in-flight updates on device
        qpts, lo, hi = _query_stream(cfg, scenario, s)
        t1 = time.perf_counter()
        knn_tickets = [batcher.submit_knn(qpts[i], cfg.k,
                                          impl=cfg.knn_impl)
                       for i in range(cfg.queries)]
        answers = [t.result() for t in knn_tickets]
        t2 = time.perf_counter()       # dispatched: host work done
        jax.block_until_ready(answers)
        t3 = time.perf_counter()       # device drained
        rec.record("knn", t3 - t1, cfg.queries, start=t1)
        rec.record("knn_dispatch", t2 - t1, cfg.queries)
        rec.record("knn_wait", t3 - t2, cfg.queries)
        t1 = time.perf_counter()
        rng_tickets = [batcher.submit_range_count(lo[i], hi[i])
                       for i in range(cfg.queries)]
        answers = [t.result() for t in rng_tickets]
        t2 = time.perf_counter()
        jax.block_until_ready(answers)
        t3 = time.perf_counter()
        rec.record("range", t3 - t1, cfg.queries, start=t1)
        rec.record("range_dispatch", t2 - t1, cfg.queries)
        rec.record("range_wait", t3 - t2, cfg.queries)
        with rec.timer("commit"):                   # exposed stall
            srv.commit()
        if s >= cfg.warmup:
            measured_updates += \
                (0 if step.delete is None else step.delete.shape[0]) + \
                (0 if step.insert is None else step.insert.shape[0])
    wall = rec.wall_s
    mem = srv.memory_report()
    out = {
        "latency_ms": rec.latency_summary(),
        "throughput": {
            "query_per_s": rec.count("knn") + rec.count("range"),
            "update_pts_per_s": measured_updates,
            "wall_s": wall,
        },
        # per-scenario memory: steady = head-version bytes at the end,
        # peak = retained-window high-water mark; all from nbytes
        # metadata (repro.obs.memory), so recording it costs no sync
        "memory": {
            "steady_bytes": mem["live_bytes"],
            "peak_window_bytes": mem["peak_window_bytes"],
            "window_bytes": mem["window_bytes"],
            "evicted_bytes": mem["evicted_bytes"],
            "evictions": mem["evictions"],
        },
        "build_s": build_s,
        "final_size": len(srv.head_index),
        "recoveries": srv.stats["recoveries"],
    }
    if mesh is not None:
        # per-shard balance report: live points per shard from the
        # key-range routing, plus the cumulative routing-drop counter
        # (0 after checked updates / commit — drops trigger replay);
        # the server's commit keeps the same as obs gauges
        sizes = np.asarray(srv.head_index.shard_sizes())
        out["distributed"] = {
            "n_shards": int(sizes.shape[0]),
            "shard_points": [int(s) for s in sizes.tolist()],
            "shard_min_points": int(sizes.min()),
            "shard_max_points": int(sizes.max()),
            "dropped": int(srv.head_index.dropped),
        }
    for key in ("query_per_s", "update_pts_per_s"):
        out["throughput"][key] = out["throughput"][key] / max(wall, 1e-9)
    if verbose:
        lat = out["latency_ms"]
        cells = " ".join(
            f"{op} p50={lat[op]['p50_ms']:7.2f} p99={lat[op]['p99_ms']:7.2f}"
            for op in ("insert", "delete", "knn", "range", "commit")
            if op in lat and lat[op]["count"])
        print(f"  [{kind}/{scenario}] {cells} | "
              f"{out['throughput']['query_per_s']:,.0f} q/s, "
              f"{out['throughput']['update_pts_per_s']:,.0f} upd-pts/s | "
              f"mem {obs.fmt_bytes(mem['live_bytes'])} steady / "
              f"{obs.fmt_bytes(mem['peak_window_bytes'])} peak",
              flush=True)
        if mesh is not None:
            d = out["distributed"]
            print(f"    shards={d['n_shards']} "
                  f"points/shard min={d['shard_min_points']} "
                  f"max={d['shard_max_points']} "
                  f"dropped={d['dropped']}", flush=True)
    return (out, srv) if return_server else out


def run(kinds=DEFAULT_KINDS, scenarios=gen.SCENARIOS,
        cfg: DriverCfg = DriverCfg(), verbose: bool = True,
        mesh=None) -> dict:
    """Sweep kinds x scenarios; returns the full json-able payload."""
    payload = {"config": dataclasses.asdict(cfg), "kinds": list(kinds),
               "scenarios": list(scenarios), "results": {}}
    for kind in kinds:
        if verbose:
            print(f"{kind}:", flush=True)
        payload["results"][kind] = {
            scenario: run_one(kind, scenario, cfg, verbose=verbose,
                              mesh=mesh)
            for scenario in scenarios}
    return payload


def _p50(stats: dict | None) -> float:
    return float((stats or {}).get("p50_ms", 0.0))


DEFAULT_ROOFLINE = "results/roofline.json"


def _cost_model_section(kind: str, counters: dict) -> dict:
    """Expected-vs-observed device time from captured plan costs.

    The obs-on run records each compiled plan's HLO byte traffic
    (``plan.cost.*``, see repro.obs.costs). Dividing the dominant kNN
    plan's bytes by the backend's kNN byte rate from the committed
    roofline baseline gives the time the cost model *expects* the
    whole kernel execution to take. Units must match: the rate comes
    from the roofline cell's own captured plan (``plan_hlo_bytes`` /
    ``time_s`` — HLO traffic over measured wall), falling back to the
    analytic ``achieved_gbytes_s`` (useful-work bytes) only for old
    baselines, where the expected time overshoots by the structure's
    ``hlo_vs_model_bytes`` factor. Compare against dispatch + device
    wait — async dispatch hides most device time inside the blocking
    ``.result()`` — to see what the model misses (queueing, launch
    gaps, cache effects). Returns nulls when nothing was captured or
    the baseline is absent."""
    costs = obs.costs.plan_costs(counters)
    out = {"plan_costs": costs, "knn_plan_sig": None,
           "knn_plan_bytes": None, "knn_expected_device_ms": None,
           "rate_source": None}
    knn = {s: c for s, c in costs.items() if s.startswith("knn.")}
    if not knn:
        return out
    sig = max(knn, key=lambda s: knn[s].get("bytes", 0))
    out["knn_plan_sig"] = sig
    out["knn_plan_bytes"] = knn[sig].get("bytes", 0)
    try:
        with open(DEFAULT_ROOFLINE) as f:
            cell = json.load(f)["results"][kind]["knn"]
    except (OSError, KeyError, TypeError, ValueError):
        return out
    if cell.get("plan_hlo_bytes") and cell.get("time_s"):
        rate = cell["plan_hlo_bytes"] / cell["time_s"]
        out["rate_source"] = f"{DEFAULT_ROOFLINE}:plan_hlo_bytes"
    else:
        rate = float(cell.get("achieved_gbytes_s", 0)) * 1e9
        out["rate_source"] = f"{DEFAULT_ROOFLINE}:model_bytes"
    if rate > 0:
        out["knn_expected_device_ms"] = \
            out["knn_plan_bytes"] / rate * 1e3
    else:
        out["rate_source"] = None
    return out


def run_attributed(kinds=DEFAULT_KINDS, scenario: str = "uniform",
                   cfg: DriverCfg = DriverCfg(),
                   verbose: bool = True) -> dict:
    """Replay one scenario per backend twice — obs disabled, then obs
    enabled — and attribute the kNN round-trip from the enabled run's
    obs data: batcher queue wait, host dispatch (plan-cache lookup +
    launch), pow2 buffer escalation, device wait. The side-by-side p50s
    are the recorded evidence that enabling obs does not regress the
    round-trip (acceptance: < 5%); the attributed segments are the
    serve-latency baseline (``results/serve_trace.json``)."""
    payload = {"config": dataclasses.asdict(cfg), "scenario": scenario,
               "kinds": list(kinds), "results": {}}
    for kind in kinds:
        assert not obs.enabled(), "attributed baseline needs obs off"
        off = run_one(kind, scenario, cfg)
        # capture_costs: the obs-on run also AOT-captures each plan's
        # flops/bytes (during warmup, where the plan misses happen, so
        # the measured percentiles never see the extra compile)
        with obs.recording(obs.Recorder(capture_costs=True)) as rec_obs:
            on = run_one(kind, scenario, cfg)
            report = rec_obs.report()
        hists, counters = report["hists"], report["counters"]
        lat_off, lat_on = off["latency_ms"], on["latency_ms"]
        p50_off, p50_on = _p50(lat_off.get("knn")), _p50(lat_on.get("knn"))
        wait = hists.get("batcher.wait_s", {})
        esc = hists.get("engine.escalation_rounds", {})
        requests = counters.get("engine.plan_request", 0)
        misses = counters.get("engine.plan_miss", 0)
        entry = {
            "obs_off": {"latency_ms": lat_off,
                        "throughput": off["throughput"]},
            "obs_on": {"latency_ms": lat_on,
                       "throughput": on["throughput"]},
            "knn_p50_ms": {"obs_off": p50_off, "obs_on": p50_on,
                           "obs_overhead_pct": 0.0 if not p50_off else
                           100.0 * (p50_on - p50_off) / p50_off},
            # round-trip attribution (ms at p50, from the obs-on run):
            # queue wait happens before dispatch, so segments sum to
            # roughly wait + round_trip for a coalesced request
            "knn_attribution_ms": {
                "batcher_wait_p50": wait.get("p50", 0.0) * 1e3,
                "dispatch_p50": _p50(lat_on.get("knn_dispatch")),
                "device_wait_p50": _p50(lat_on.get("knn_wait")),
                "round_trip_p50": p50_on,
            },
            "plan_cache": {
                "requests": requests, "misses": misses,
                "hit_rate": 0.0 if not requests else
                (requests - misses) / requests,
                "traces": counters.get("engine.trace", 0),
            },
            "escalation": {
                "calls": esc.get("count", 0),
                "rounds_p50": esc.get("p50", 0.0),
                "rounds_max": esc.get("max", 0.0),
                "extra_rounds": counters.get("engine.escalation", 0),
            },
            "batcher": {
                "coalesce_rows_p50":
                    hists.get("batcher.coalesce_rows", {}).get("p50", 0.0),
                "pad_rows_p50":
                    hists.get("batcher.pad_rows", {}).get("p50", 0.0),
                "flushes": {k.split(".", 2)[2]: v
                            for k, v in counters.items()
                            if k.startswith("batcher.flush.")},
            },
            # expected (plan-cost model x roofline rate) vs observed
            # device wait; see _cost_model_section
            "cost_model": {
                **_cost_model_section(kind, counters),
                "knn_device_wait_observed_ms":
                    _p50(lat_on.get("knn_wait")),
            },
            "memory": {"obs_off": off.get("memory"),
                       "obs_on": on.get("memory")},
        }
        payload["results"][kind] = entry
        if verbose:
            a = entry["knn_attribution_ms"]
            print(f"[{kind}/{scenario}] knn p50 obs_off={p50_off:.2f}ms "
                  f"obs_on={p50_on:.2f}ms "
                  f"({entry['knn_p50_ms']['obs_overhead_pct']:+.1f}%) | "
                  f"wait={a['batcher_wait_p50']:.2f} "
                  f"dispatch={a['dispatch_p50']:.2f} "
                  f"device={a['device_wait_p50']:.2f}", flush=True)
    return payload


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--kinds", default=",".join(DEFAULT_KINDS),
                    help="comma-separated registered backends")
    ap.add_argument("--scenarios", default=",".join(gen.SCENARIOS),
                    help=f"comma-separated from {gen.SCENARIOS}")
    ap.add_argument("--n", type=int, default=DriverCfg.n)
    ap.add_argument("--batch", type=int, default=DriverCfg.batch)
    ap.add_argument("--steps", type=int, default=DriverCfg.steps)
    ap.add_argument("--warmup", type=int, default=DriverCfg.warmup)
    ap.add_argument("--queries", type=int, default=DriverCfg.queries)
    ap.add_argument("--k", type=int, default=DriverCfg.k)
    ap.add_argument("--window", type=int, default=DriverCfg.window)
    ap.add_argument("--max-delay-ms", type=float,
                    default=DriverCfg.max_delay_ms)
    ap.add_argument("--seed", type=int, default=DriverCfg.seed)
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="serve from a DistributedIndex sharded over the "
                    "first N devices and add per-shard metrics: the "
                    "accelerator's chips where one is present, else N "
                    "simulated CPU devices (stages "
                    "--xla_force_host_platform_device_count before jax "
                    "initializes)")
    ap.add_argument("--json", nargs="?", const=DEFAULT_JSON, default=None,
                    metavar="PATH", help="write the latency/throughput "
                    f"payload (default {DEFAULT_JSON})")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny end-to-end trace for CI: one backend, "
                    "every scenario, seconds not minutes")
    ap.add_argument("--obs-trace", nargs="?", const=DEFAULT_OBS_TRACE,
                    default=None, metavar="PATH",
                    help="record the run through repro.obs and export a "
                    "chrome trace (view: python -m repro.obs.view PATH; "
                    f"default {DEFAULT_OBS_TRACE})")
    ap.add_argument("--attributed", nargs="?", const=DEFAULT_SERVE_TRACE,
                    default=None, metavar="PATH",
                    help="obs-off vs obs-on side-by-side on the first "
                    "--scenarios entry, with the kNN round-trip broken "
                    "into batcher-wait/dispatch/device segments "
                    f"(default {DEFAULT_SERVE_TRACE})")
    args = ap.parse_args(argv)
    from ..configs import platform
    mesh = None
    if args.mesh:
        # must precede anything that initializes the jax backend (the
        # module-level jax import above is fine — topology locks at the
        # first devices()/array op, not at import)
        mesh = platform.simulate_mesh(args.mesh)
    platform.use_compile_cache()
    rec_obs = obs.install(obs.Recorder()) if args.obs_trace else None

    def _export_obs():
        if rec_obs is None:
            return
        os.makedirs(os.path.dirname(args.obs_trace) or ".", exist_ok=True)
        obs.write_chrome_trace(rec_obs, args.obs_trace)
        obs.uninstall()
        print(f"wrote obs chrome trace -> {args.obs_trace} "
              f"(view: python -m repro.obs.view {args.obs_trace})")

    if args.smoke:
        cfg = DriverCfg(n=1500, batch=128, steps=2, warmup=1, queries=16,
                        k=5, seed=args.seed, mesh=args.mesh)
        payload = run(kinds=("spac-h",), scenarios=gen.SCENARIOS, cfg=cfg,
                      mesh=mesh)
        ops = {op for r in payload["results"]["spac-h"].values()
               for op, s in r["latency_ms"].items() if s["count"]}
        assert {"insert", "delete", "knn", "range", "commit"} <= ops, ops
        if mesh is not None:
            for r in payload["results"]["spac-h"].values():
                d = r["distributed"]
                assert d["n_shards"] == args.mesh, d
                assert sum(d["shard_points"]) == r["final_size"], d
        _export_obs()
        if args.json:   # the perf-regression gate replays this payload
            os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
            with open(args.json, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            print(f"wrote smoke payload -> {args.json}")
        print("serving driver smoke OK")
        return
    cfg = DriverCfg(n=args.n, batch=args.batch, steps=args.steps,
                    warmup=args.warmup, queries=args.queries, k=args.k,
                    window=args.window, max_delay_ms=args.max_delay_ms,
                    seed=args.seed, mesh=args.mesh)
    if args.attributed:
        assert rec_obs is None, \
            "--attributed manages its own recorder; drop --obs-trace"
        assert mesh is None, \
            "--attributed compares obs on/off single-device; drop --mesh"
        scenario = args.scenarios.split(",")[0]
        payload = run_attributed(kinds=tuple(args.kinds.split(",")),
                                 scenario=scenario, cfg=cfg)
        os.makedirs(os.path.dirname(args.attributed) or ".",
                    exist_ok=True)
        with open(args.attributed, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        print(f"wrote attributed serve baseline -> {args.attributed}")
        return
    payload = run(kinds=args.kinds.split(","),
                  scenarios=args.scenarios.split(","), cfg=cfg, mesh=mesh)
    _export_obs()
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        print(f"wrote serving latency percentiles -> {args.json}")


if __name__ == "__main__":
    main()
