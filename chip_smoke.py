"""Bring-up smoke run of the served spatial index on TPU chips.

Drives the serving stack's main path once, through the entry point the
workload driver uses (``repro.serving.driver.run_one``: ``make_index`` ->
``SpatialServer`` -> ``MicroBatcher`` -> ``QueryEngine`` -> the Pallas
kNN kernels and the update programs), for the ``spac-h`` and ``porth``
backends: 2-D uniform int32 points, a 1% churn trace, 2 warm-up and 3
measured steps of 256 kNN (k=10) and 256 range-count requests each,
version window 4. After the last ``commit()`` it checks the server
against a host numpy brute force over ``extract_points()``: the exact
multiset of live points (bootstrap + inserts - deletes), 32 kNN and 32
range-count answers.

  python chip_smoke.py             # one chip, n = 4,000,000 per backend
  python chip_smoke.py --chips 4   # spac-h DistributedIndex over 4 chips, n = 16,000,000
  JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]
                                   # tiny CPU rehearsal, Pallas interpret mode

A chip run prints, last, one JSON line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Where JAX finds no TPU, or any phase or check fails, it exits non-zero
and prints no such line. A rehearsal never prints it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SCENARIO = "uniform"
K = 10
CHECKS = 32
F32_EXACT = 1 << 24


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: serve a DistributedIndex over four chips "
                    "(4,000,000 points per chip) and run nothing else")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at a tiny size with the Pallas "
                    "interpret spellings; prints no ok line")
    return ap.parse_args(argv)


def _import_repro():
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        sys.exit(f"chip_smoke: no repro package under {src}: run from a "
                 f"checkout of the repository")
    sys.path.insert(0, str(src))


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading from
    the persistent cache), read from its own monitoring events."""

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.total += duration


class Phases:
    """Wall and compile seconds per named phase, printed as they end."""

    def __init__(self, clock: CompileClock):
        self.clock = clock
        self.rows = []

    def run(self, name, fn, *args, **kw):
        c0, t0 = self.clock.total, time.perf_counter()
        out = fn(*args, **kw)
        wall = time.perf_counter() - t0
        comp = self.clock.total - c0
        self.rows.append((name, wall, comp))
        print(f"phase {name}: wall {wall:.3f} s, compile {comp:.3f} s",
              flush=True)
        return out


def _require(cond, *what):
    """Raise on a failed check (unlike ``assert``, never optimised away)."""
    if not cond:
        raise AssertionError(" ".join(map(str, what)) or "check failed")


def _keys(pts, hi):
    """One int64 key per 2-D/3-D integer point in [0, hi)^D."""
    import numpy as np
    key = np.zeros(pts.shape[0], np.int64)
    for d in range(pts.shape[1]):
        key = key * hi + pts[:, d].astype(np.int64)
    return key


def _expected_multiset(trace, hi):
    """(sorted unique keys, counts) of bootstrap + inserts - deletes, with
    the index's multiset semantics (a delete removes one copy; deleting
    an absent point is a no-op)."""
    import numpy as np
    keys, counts = np.unique(_keys(np.asarray(trace.bootstrap), hi),
                             return_counts=True)
    counts = counts.astype(np.int64)
    for step in trace.steps:
        if step.delete is not None:
            dk, dc = np.unique(_keys(np.asarray(step.delete), hi),
                               return_counts=True)
            pos = np.searchsorted(keys, dk)
            hit = pos < keys.shape[0]
            hit[hit] = keys[pos[hit]] == dk[hit]
            counts[pos[hit]] = np.maximum(counts[pos[hit]] - dc[hit], 0)
        if step.insert is not None:
            ik, ic = np.unique(_keys(np.asarray(step.insert), hi),
                               return_counts=True)
            keys = np.concatenate([keys, ik])
            counts = np.concatenate([counts, ic])
            keys, inv = np.unique(keys, return_inverse=True)
            counts = np.bincount(inv, weights=counts).astype(np.int64)
    keep = counts > 0
    return keys[keep], counts[keep]


def check_server(srv, trace, cfg, hi):
    """Exactness of the committed head against a host brute force; returns
    printable result lines, raises AssertionError on any mismatch."""
    import jax
    import numpy as np
    from repro.data import points as gen

    head = srv.head_index
    pts, ok = head.extract_points()
    live = np.asarray(pts)[np.asarray(ok)]
    size = len(head)
    lines = []

    # every acknowledged write is read back: the exact multiset
    want_keys, want_counts = _expected_multiset(trace, hi)
    got_keys, got_counts = np.unique(_keys(live, hi), return_counts=True)
    want_size = int(want_counts.sum())
    _require(size == live.shape[0] == want_size, "size", size,
             live.shape[0], want_size)
    _require(np.array_equal(got_keys, want_keys)
             and np.array_equal(got_counts, want_counts),
             "live multiset differs from the expected one")
    lines.append(f"final size {size} == expected {want_size}; live "
                 f"multiset equal ({want_keys.shape[0]} distinct points)")

    snap = srv.snapshot()
    key = jax.random.PRNGKey(cfg.seed + 1001)
    k1, k2 = jax.random.split(key)
    qs = np.asarray(gen.uniform(k1, CHECKS, cfg.dim, hi))
    d2, nbrs, valid = snap.knn_points(qs, K, impl=cfg.knn_impl)
    d2, nbrs, valid = np.asarray(d2), np.asarray(nbrs), np.asarray(valid)
    live64 = live.astype(np.int64)
    inexact = 0
    for i in range(CHECKS):
        bf = ((live64 - qs[i].astype(np.int64)) ** 2).sum(-1)
        want = np.sort(np.partition(bf, K - 1)[:K])
        got = d2[i].astype(np.int64)
        _require(valid[i].all(), "kNN", i, "invalid hits", valid[i])
        # f32 distances are exact below 2^24; above it (only at sizes
        # far below a deployment's) they may round by an ulp or two
        tol = np.where(want < F32_EXACT, 0, want >> 22)
        inexact += int((want >= F32_EXACT).sum())
        _require((np.abs(got - want) <= tol).all(), "kNN", i, "d2", got,
                 want)
        back = ((nbrs[i].astype(np.int64) - qs[i]) ** 2).sum(-1)
        _require(np.array_equal(back, want), "kNN", i, "points", back,
                 want)
        _require(np.isin(_keys(nbrs[i], hi), got_keys).all(), "kNN", i,
                 "neighbour not a live point")
    lines.append(f"kNN k={K}: {CHECKS} queries exact: neighbour points, "
                 f"and distances ({inexact} of {CHECKS * K} above 2^24, "
                 f"compared to f32 rounding)")

    lo, hi_box = gen.query_boxes(k2, CHECKS, cfg.dim, hi // cfg.box_frac,
                                 hi)
    lo, hi_box = np.asarray(lo), np.asarray(hi_box)
    cnt = np.asarray(snap.range_count(lo, hi_box))
    for i in range(CHECKS):
        want = int(((live >= lo[i]) & (live <= hi_box[i])).all(-1).sum())
        _require(int(cnt[i]) == want, "range", i, int(cnt[i]), want)
    lines.append(f"range_count: {CHECKS} boxes exact (counts "
                 f"{int(cnt.min())}..{int(cnt.max())})")
    return lines


def _kernel_in_program(srv, cfg, mesh):
    """True when the served kNN program embeds a compiled Mosaic kernel
    (``tpu_custom_call``), i.e. ``auto`` reached Pallas, not ``ref``."""
    import jax
    import jax.numpy as jnp
    from repro.core import distributed as D
    from repro.core import engine as E
    head = srv.head_index
    q = jax.ShapeDtypeStruct((cfg.queries, cfg.dim), jnp.int32)
    pts = head.tree.pts
    route, param = head.engine.plan_knn(pts.shape[-3], pts.shape[-2],
                                        cfg.knn_impl)
    if mesh is None:
        fn = E._knn_closure(cfg.queries, cfg.dim, "int32", K, route, param)
        lowered = fn.lower(head.view(), q)
    else:
        fn = D._knn_closure(mesh, head.index.axis, K, route, param, 8)
        lowered = fn.lower(head.tree, q)
    return route, "tpu_custom_call" in lowered.as_text()


def serve_and_check(kind, n, mesh, args, phases, hi):
    from repro import obs
    from repro.data import points as gen
    from repro.serving.driver import DriverCfg, run_one

    cfg = DriverCfg(n=n, batch=n // 100, steps=3, warmup=2, queries=256,
                    k=K, window=4, seed=args.seed,
                    mesh=0 if mesh is None else mesh.shape["data"],
                    knn_impl=("pallas-frontier-interpret" if args.rehearse
                              else "auto"))
    print(f"== {kind}: n={n:,} batch={cfg.batch:,} steps={cfg.warmup}+"
          f"{cfg.steps} requests/step={cfg.queries} kNN + {cfg.queries} "
          f"range_count, window={cfg.window}, chips="
          f"{1 if mesh is None else mesh.shape['data']}", flush=True)
    with obs.recording(obs.Recorder()) as rec:
        out, srv = phases.run(f"{kind}.serve", run_one, kind, SCENARIO, cfg,
                              mesh=mesh, return_server=True)
        routes = {k: v for k, v in rec.counters.items()
                  if k.startswith("engine.route.")}
    head = srv.head_index
    shape = tuple(head.tree.pts.shape)
    print(f"{kind}: rows x slots = {' x '.join(map(str, shape[:-1]))} "
          f"(leaf tree pts {shape}), {head.nbytes:,} bytes per version, "
          f"peak window {out['memory']['peak_window_bytes']:,} bytes",
          flush=True)
    print(f"{kind}: build {out['build_s']:.3f} s; overflow recoveries "
          f"{out['recoveries']}; kNN routes "
          f"{json.dumps(routes, sort_keys=True)}", flush=True)
    route, kernel = _kernel_in_program(srv, cfg, mesh)
    print(f"{kind}: served kNN route {route!r}, compiled Mosaic kernel in "
          f"the program: {kernel}", flush=True)
    lat = out["latency_ms"]
    print(f"{kind}: p50 ms " + " ".join(
        f"{op}={lat[op]['p50_ms']:.3f}"
        for op in ("insert", "delete", "knn", "range", "commit")
        if lat.get(op, {}).get("count")), flush=True)
    if mesh is not None:
        d = out["distributed"]
        print(f"{kind}: live points per shard {d['shard_points']} "
              f"(dropped {d['dropped']})", flush=True)
        _require(min(d["shard_points"]) > 0, "empty shard", d)
        _require(sum(d["shard_points"]) == out["final_size"], d)
    trace = gen.make_trace(SCENARIO, seed=cfg.seed, n=cfg.n,
                           batch=cfg.batch, steps=cfg.warmup + cfg.steps,
                           dim=cfg.dim)
    lines = phases.run(f"{kind}.check", check_server, srv, trace, cfg, hi)
    for line in lines:
        print(f"{kind}: {line}", flush=True)
    if not args.rehearse:
        _require(route == "pallas-frontier", "served kNN route", route)
        _require(kernel, "kNN program has no compiled Mosaic kernel")


def main(argv=None):
    args = _parse(argv)
    _import_repro()
    from repro.configs import platform
    if args.rehearse and args.chips > 1:
        platform.stage(host_device_count=args.chips)
    import jax
    from repro.data import points as gen

    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    print(f"device: {json.dumps(device)}", flush=True)
    if dev.platform != "tpu" and not args.rehearse:
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{dev.platform!r}); nothing was run")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX sees {len(devs)}")
    cache = platform.use_compile_cache()
    print(f"compile cache: {cache}", flush=True)

    n = 20_000 if args.rehearse else 4_000_000 * args.chips
    # four chips: spac-h only. A porth shard sized for 4M points with
    # the 2x routing headroom holds 2.08M rows, and its range-count temp
    # alone (9.0 GB) plus the retained versions leaves no margin on a
    # 16 GB chip (described-chip compile)
    kinds = ["spac-h", "porth"] if args.chips == 1 else ["spac-h"]
    mesh = platform.device_mesh(args.chips) if args.chips > 1 else None
    phases = Phases(CompileClock())
    t0 = time.perf_counter()
    for kind in kinds:
        serve_and_check(kind, n, mesh, args, phases, gen.DEFAULT_HI)
    total_wall = time.perf_counter() - t0
    print(f"total: wall {total_wall:.3f} s, compile "
          f"{sum(c for _, _, c in phases.rows):.3f} s", flush=True)
    if args.rehearse:
        print(f"rehearsal passed on {dev.platform} (not a chip run)")
        return
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
