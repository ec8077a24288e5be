"""Find the highest request rate an open-loop cell sustains (its knee).

  python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
      --rates 150,200,250

Builds the cell once, then runs one window per rate, in order, with the
mix's rate replaced. For each it prints the p50 and p95 of each op, the
generator's lateness, and the growth of the backlog: the median latency
of the requests due in the window's last quarter over that of its first
quarter. A rate the server sustains keeps that near 1; above the knee
the queue grows all through the window. The knee found is written into
the mix's file by hand (a cell's rate is fixed, never searched for in a
benchmark run). Needs the chip, like ``bench/run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from bench import spec
    cell = spec.load(ROOT, args.workload)
    if cell.mix["loop"] != "open":
        sys.exit("sweep: the cell's mix is not an open loop")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import numpy as np
    from bench import loops
    from bench.stream import OPS
    from repro.configs import platform
    if jax.devices()[0].platform != "tpu":
        sys.exit("sweep: JAX found no TPU; nothing was run")
    platform.use_compile_cache()
    loop = loops.Serve(cell.config, dict(cell.mix), args.seed,
                       loops.Annotations(False))
    for rate in (float(r) for r in args.rates.split(",")):
        loop.mix["rate_per_s"] = rate
        loop.window(args.seconds)
        loop.kept.clear()
        row = {"rate_per_s": rate, **loop.info()}
        for op in OPS:
            idx = loop._counted(op)
            due = loop.sch.t[idx[~np.isnan(loop.done_t[idx])]]
            lat = loop.latencies_ms(op)
            q = args.seconds / 4
            first = np.median(lat[due < q])
            last = np.median(lat[due >= 3 * q])
            row[op]["growth"] = float(last / first)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
