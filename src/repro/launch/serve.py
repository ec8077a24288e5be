"""Serving launcher: the paper's dynamic-index service + LM serving.

Two services behind one CLI:

  * ``--service index`` — a thin CLI over the versioned serving runtime
    (:mod:`repro.serving`): snapshot-isolated queries pipelined against
    async-dispatched updates, micro-batched through the QueryEngine's
    cached plans, with per-op p50/p95/p99 from the workload driver.
    The driver separates warmup from measured reps, so the reported
    percentiles exclude jit compiles and the engine's pow2
    bucket-escalation retraces (the old synchronous loop here timed
    both into its first batch).
  * ``--service lm`` — batched LM serving (prefill + greedy decode) on
    a reduced config, exercising the same serve_step the dry-run lowers
    at production shapes.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --service index \
      --n 100000 --batches 20 --queries 1000
  PYTHONPATH=src python -m repro.launch.serve --service lm \
      --arch qwen1.5-0.5b --batch 4 --new 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs import platform
from repro.data import points as gen
from repro.models import transformer
from repro.serve import ServeEngine
from repro.serving import driver as serving_driver


def serve_index(args):
    """Replay the churn trace for (--dist, --kind) through the serving
    runtime; ``--scenario`` picks any other registered trace shape."""
    scenario = args.scenario or args.dist
    # churn bootstraps half of --n and streams in the rest; for the
    # dynamic shapes --n is the object/window count itself
    n = args.n // 2 if scenario in gen.GENERATORS else args.n
    cfg = serving_driver.DriverCfg(
        n=n, batch=max(args.n // (2 * args.batches), 16),
        steps=args.batches, warmup=min(2, max(args.batches // 2, 1)),
        queries=args.queries, k=args.k, seed=args.seed)
    payload = serving_driver.run(kinds=(args.kind,),
                                 scenarios=(scenario,), cfg=cfg,
                                 verbose=True)
    res = payload["results"][args.kind][scenario]
    thr = res["throughput"]
    print(f"index service [{scenario}/{args.kind}] n={args.n}: "
          f"build {res['build_s']:.2f}s | "
          f"{thr['query_per_s']:,.0f} q/s | "
          f"{thr['update_pts_per_s']:,.0f} update-pts/s | "
          f"final size {res['final_size']} | "
          f"recoveries {res['recoveries']}")


def serve_lm(args):
    cfg = configs.smoke(args.arch).with_(act_dtype="float32")
    params = transformer.init_params(jax.random.PRNGKey(args.seed), cfg)
    engine = ServeEngine(cfg, params, max_len=args.prompt + args.new)
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt), 0, cfg.vocab,
        dtype=jnp.int32)
    t0 = time.time()
    out = engine.generate(prompts, args.new)
    jax.block_until_ready(out)
    dt = time.time() - t0
    print(f"lm serving [{cfg.name}]: batch={args.batch} prompt={args.prompt}"
          f" +{args.new} new -> {out.shape}, "
          f"{args.batch * args.new / dt:,.1f} tok/s")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--service", choices=["index", "lm"], default="index")
    ap.add_argument("--seed", type=int, default=0)
    # index service
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--batches", type=int, default=10)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--dist", default="uniform",
                    choices=list(gen.GENERATORS))
    ap.add_argument("--kind", default="spac-h",
                    help="registered index backend (see repro.core)")
    ap.add_argument("--scenario", default=None,
                    choices=list(gen.SCENARIOS),
                    help="trace shape (default: churn over --dist); "
                         "moving-objects / sliding-window etc.")
    # lm service
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=32)
    ap.add_argument("--new", type=int, default=16)
    args = ap.parse_args(argv)
    platform.use_compile_cache()
    (serve_index if args.service == "index" else serve_lm)(args)


if __name__ == "__main__":
    main()
