"""Shared by the benchmark's CPU rehearsal tests: run a cell end to end
in this process at a tiny size, with the Pallas kernels interpreted."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

# the configuration's density of points (4M in [0, 2^20)^2) at 16,000
# points, so that kNN distances stay inside f32's exact range
TINY = {"config": {"n": 16000, "hi": 65536},
        "knn_impl": "pallas-frontier-interpret"}


def rehearse(cell: str, seed: int, seconds: float = 2.0, *extra: str):
    from bench import run
    return run.main(["--workload", cell, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", "0", *extra],
                    rehearse=TINY)


def no_result_line(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())
