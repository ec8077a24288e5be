"""BENCHMARK.json and the files it names; how the harness finds them."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_rehearse import ROOT, TINY

from bench import loops, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_name_has_its_files_and_every_cell_its_metrics():
    s = _spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for c in s["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg["reduced"])
    for m in s["end_to_end"] + s["per_layer"]:
        assert NAME.match(m["name"])
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    e2e = {m["name"] for m in s["end_to_end"]}
    for m in s["per_layer"]:
        assert m["moves"] in e2e
    for w in s["workloads"]:
        cell = spec.load(ROOT, w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
            assert callable(cell.reader(m["name"]))


# later deployments as files only: (cell, base config, config changes,
# base mix, mix changes); the varden and moving-object ones are ROADMAP
# Reach deployments 4 and 2
NEW_CELLS = {
    "spacz-ingest": ("uniform2d-spach-4m", {"index": "spac-z"}, "ingest",
                     {}),
    "varden-hot-serve": (
        "uniform2d-spach-4m",
        {"data": {"kind": "varden", "step": 50, "restart_p": 0.01}},
        "serve",
        {"queries": {"kind": "hot", "centres": 64, "zipf_s": 1.0,
                     "spread": 512},
         "arrivals": {"kind": "bursty", "burst": 8}, "rate_per_s": 200,
         "update_every_s": 0.3, "max_delay_ms": 50, "warm_rows": [16],
         "warm_rounds": 1}),
    "moving-ingest": ("uniform2d-porth-4m", {}, "ingest",
                      {"updates": {"kind": "moving", "disp": 2000}}),
}


@pytest.mark.parametrize("name", sorted(NEW_CELLS))
def test_a_new_configuration_is_found_by_name_with_no_edit(tmp_path, name):
    """A later change adds a configuration, a mix and a cell as files and
    entries only; the harness finds and runs them by name, and their
    check reads them as correct."""
    base_cfg, cfg_change, base_mix, mix_change = NEW_CELLS[name]
    s = _spec()
    twin = {w["traffic"]: w["name"] for w in s["workloads"]}[base_mix]
    s["configs"].append({"name": f"{name}-cfg", "source": "test",
                         "file": f"bench/configs/{name}-cfg.json",
                         "reduced": ["n"], "why": "test"})
    s["workloads"].append({"name": name, "config": f"{name}-cfg",
                           "traffic": f"{name}-mix", "chips": 1,
                           "why": "test"})
    for m in s["end_to_end"] + s["per_layer"]:
        if twin in m.get("workloads", []):
            m["workloads"].append(name)
    (tmp_path / "bench").mkdir()
    for d in ("traffic", "metrics", "configs"):
        shutil.copytree(ROOT / "bench" / d, tmp_path / "bench" / d)
    cfg = json.loads((ROOT / "bench/configs" / f"{base_cfg}.json")
                     .read_text())
    cfg.update(name=f"{name}-cfg", **cfg_change)
    (tmp_path / "bench/configs" / f"{name}-cfg.json").write_text(
        json.dumps(cfg))
    mix = json.loads((ROOT / "bench/traffic" / f"{base_mix}.json")
                     .read_text())
    mix.update(mix_change)
    (tmp_path / "bench/traffic" / f"{name}-mix.json").write_text(
        json.dumps(mix))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(s))

    cell = spec.load(tmp_path, name)
    assert cell.config["name"] == f"{name}-cfg" and cell.mix == mix
    assert ({m["name"] for m in cell.end_to_end}
            == {m["name"] for m in spec.load(ROOT, twin).end_to_end})
    Loop = loops.Ingest if mix["loop"] == "closed" else loops.Serve
    loop = Loop(dict(cell.config, **TINY["config"]), cell.mix, 3,
                loops.Annotations(False), TINY["knn_impl"])
    loop.window(1.0)
    assert loop.attempted() > 0
    assert set(loop.check().values()) == {0}


def _run_alone(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "porth-ingest",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=cwd,
        env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["checkout", "benchmark_files_only"])
def test_no_result_without_a_tpu_or_without_the_program(tmp_path, where):
    if where == "checkout":
        cwd = ROOT
    else:
        cwd = tmp_path
        shutil.copy(ROOT / "BENCHMARK.json", cwd)
        for p in _spec()["paths"]:
            shutil.copytree(ROOT / p, cwd / p,
                            ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_alone(cwd, {})
    assert out.returncode != 0
    assert not any(line.startswith("{") for line in out.stdout.splitlines())
