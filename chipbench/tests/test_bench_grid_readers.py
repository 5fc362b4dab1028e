"""The grid cell's readers: ``partition_ms``, ``a2a_fill_pct`` and
``a2a_mb`` read a number from a whole tiny grid run on four host devices
and None from a program without the recorder or without the counters;
``exchange_ms`` reads device 0's collective time from a trace, and None
where no collective ran."""
import json
import os
import subprocess
import sys
import textwrap
import types

import pytest

from chipbench import run, trace
from chipbench.tests import _tiny

COUNTED = ("partition_ms", "a2a_fill_pct", "a2a_mb")

_READ_GRID_RUN = textwrap.dedent("""
    import json, pathlib, sys, types
    from chipbench import run
    from chipbench.tests import _tiny
    from repro.core import api, graph, telemetry
    metrics = sys.argv[2].split(",")
    readers = {m: run.load_reader(run.BENCH_DIR, m) for m in metrics}
    root, bench_dir = _tiny.bench_root(pathlib.Path(sys.argv[1]))
    line = _tiny.run_cell(root, bench_dir, _tiny.GRID)
    ctx = types.SimpleNamespace(solves=[None] * 3, n=256, trace=None)
    out = {"line": line, "grid": {m: r.read(ctx) for m, r in readers.items()},
           "counters": telemetry.recent(3)}
    # a solve on one chip: no partition, no exchange
    g = graph.generate(64, avg_degree=4.0, kind="uniform", seed=1)
    api.solve(api.MatchingProblem.from_graph(g))
    one = types.SimpleNamespace(solves=[None], n=64, trace=None)
    out["one_chip"] = {m: r.read(one) for m, r in readers.items()}
    # a program without the recorder
    import repro.core
    del repro.core.telemetry
    sys.modules["repro.core.telemetry"] = None
    out["no_recorder"] = {m: r.read(ctx) for m, r in readers.items()}
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def grid_read(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(_tiny.REPO),
                                           str(_tiny.REPO / "src")]))
    p = subprocess.run(
        [sys.executable, "-c", _READ_GRID_RUN,
         str(tmp_path_factory.mktemp("grid") / "root"), ",".join(COUNTED)],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("metric", COUNTED)
def test_reader_reads_the_tiny_grid_run(grid_read, metric):
    assert grid_read["line"]["correct"] is True, grid_read["line"]["checks"]
    value = grid_read["grid"][metric]
    assert isinstance(value, float) and value > 0, (metric, value)
    counters = [r["counters"] for r in grid_read["counters"]]
    slots = sum(c["a2a_slots"] for c in counters)
    if metric == "a2a_fill_pct":
        assert value == pytest.approx(
            100 * sum(c["a2a_filled"] for c in counters) / slots)
        assert value <= 100
    elif metric == "a2a_mb":
        # 13 bytes a slot on the unpacked path: i, j, w and the validity
        assert value == pytest.approx(13e-6 * slots / len(counters))


@pytest.mark.parametrize("metric", COUNTED)
def test_reader_reads_none_without_the_recorder(grid_read, metric):
    assert grid_read["no_recorder"][metric] is None
    # a solve that partitions nothing and exchanges nothing
    assert grid_read["one_chip"][metric] is None


EXCHANGE = run.load_reader(run.BENCH_DIR, "exchange_ms")


def _trace(ops, steps=2):
    spans = []
    for k in range(steps):
        spans.append((k * 10e6, k * 10e6 + 1e6, "host-in"))
        spans.append((k * 10e6 + 1e6, k * 10e6 + 9e6, "solve"))
    return trace.Trace({0: trace.Device(0, ops, [])}, spans)


def test_exchange_ms_reads_collective_time_per_solve():
    # the TPU's trace spells all-to-alls and psums as the JAX primitives
    ops = [(1e6, 2e6, "fusion.3"),
           (2e6, 2.5e6, "all-gather.1"),
           (2.4e6, 3e6, "all_to_all.2"),  # overlaps: counted once
           (11e6, 11.5e6, "all-reduce.4"),
           (11.5e6, 12e6, "psum.6"),
           (12e6, 13e6, "fusion.5")]
    ctx = types.SimpleNamespace(trace=_trace(ops))
    assert EXCHANGE.read(ctx) == pytest.approx((1.0 + 1.0) / 2)


def test_exchange_ms_reads_none_without_collectives():
    ctx = types.SimpleNamespace(trace=_trace([(1e6, 2e6, "fusion.3")]))
    assert EXCHANGE.read(ctx) is None
    assert EXCHANGE.read(types.SimpleNamespace(trace=None)) is None
