"""A copy of the benchmark's data at a size the CPU runs in seconds, and a
stand-in for the harness's look for a chip, for tests that drive whole runs
on the CPU. The copy's peaks table names the CPU's device kind; the real
table stays TPU-only."""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = REPO / "chipbench"
ONE = "uniform-d16.refactor-cold"
GRID = "uniform-d16-grid2x2.refactor-cold"


def bench_root(tmp: pathlib.Path, n: int = 256) -> tuple[pathlib.Path, pathlib.Path]:
    """``tmp`` holding BENCHMARK.json, the program's ``src`` (linked) and
    ``chipbench``'s data at ``n`` rows. Returns (root, bench_dir)."""
    bench_dir = tmp / "chipbench"
    bench_dir.mkdir(parents=True)
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(BENCH / sub, bench_dir / sub)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    if GRID not in {c["name"] for c in bench["workloads"]}:
        # the grid route is driven on host devices even where the
        # benchmark holds no grid cell
        bench["workloads"].append({"name": GRID, "config": "uniform-d16-grid2x2",
                                   "traffic": "refactor-cold", "chips": 4,
                                   "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp / "src").symlink_to(REPO / "src")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (bench_dir / "peaks.json").write_text(json.dumps(peaks))
    for path in (bench_dir / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["n"] = n
        if "cap" in cfg["solve_options"]:
            cfg["solve_options"]["cap"] = 5 * n  # a block holds about 4n edges
        path.write_text(json.dumps(cfg))
    traffic = bench_dir / "traffic" / "refactor-cold.json"
    mix = json.loads(traffic.read_text())
    mix["chain_length"] = 4
    traffic.write_text(json.dumps(mix))
    return tmp, bench_dir


def cpu_devices(chips: int):
    import jax

    devices = jax.devices()
    return jax, {"platform": devices[0].platform,
                 "kind": devices[0].device_kind, "count": len(devices)}


def run_cell(root, bench_dir, cell: str = ONE, seed: int = 2**31 + 3,
             seconds: float = 0.3):
    from chipbench import run

    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=0)
    line, _ = run.run(args, root=root, bench_dir=bench_dir,
                      start_jax=cpu_devices)
    return line
