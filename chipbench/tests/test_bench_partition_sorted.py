"""The ``partition_sorted`` reader: the instances per solve that the grid's
host partition split into blocks by its linear pass, read from the
solves' records; None from a solve that partitions nothing and from a
program without the recorder. Grid solves run on a 1x1 mesh of the host's
one device, which partitions as any grid does."""
import sys
import types

import pytest

from chipbench import run
from chipbench.tests import _tiny

READER = run.load_reader(run.BENCH_DIR, "partition_sorted")


def _grid_solves(b: int, solves: int = 2):
    from repro.core import api, dist, graph

    gs = [graph.generate(64, avg_degree=4.0, kind="uniform", seed=s)
          for s in range(b)]
    problem = (api.MatchingProblem.from_graph(gs[0]) if b == 1
               else api.MatchingProblem.stack(gs))
    options = api.SolveOptions(grid=dist.make_mesh((1, 1)))
    for _ in range(solves):
        api.solve(problem, options)
    return types.SimpleNamespace(solves=[None] * solves, n=64, trace=None)


@pytest.mark.parametrize("b", [1, 3])
def test_reader_reads_the_sorted_instances_of_each_grid_solve(b):
    assert READER.read(_grid_solves(b)) == float(b)


def test_reader_reads_none_where_nothing_is_partitioned():
    from repro.core import api, graph

    _grid_solves(1)
    g = graph.generate(64, avg_degree=4.0, kind="uniform", seed=1)
    api.solve(api.MatchingProblem.from_graph(g))
    one = types.SimpleNamespace(solves=[None], n=64, trace=None)
    assert READER.read(one) is None
    # and over a grid solve and a one-chip solve together
    both = types.SimpleNamespace(solves=[None] * 2, n=64, trace=None)
    assert READER.read(both) is None
    assert READER.read(types.SimpleNamespace(solves=[], n=64,
                                             trace=None)) is None


def test_reader_reads_none_without_the_recorder(monkeypatch):
    import repro.core

    ctx = _grid_solves(1)
    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    assert READER.read(ctx) is None


def test_metric_is_read_in_the_grid_cell_only():
    bench = run.load_benchmark()
    entry = [m for m in bench["per_layer"] if m["name"] == "partition_sorted"]
    assert entry == [{"name": "partition_sorted", "unit": "instances",
                      "better": "higher", "source": "program_counter",
                      "layer": "facade and host prep", "moves": "solve_s",
                      "workloads": [_tiny.GRID]}]
    assert bench["per_layer"][-1] is entry[0]  # appended, nothing moved
    names = {cell: [m["name"] for m in run.cell_metrics(bench, "per_layer",
                                                        cell)]
             for cell in (_tiny.ONE, _tiny.GRID)}
    assert "partition_sorted" in names[_tiny.GRID]
    assert "partition_sorted" not in names[_tiny.ONE]
