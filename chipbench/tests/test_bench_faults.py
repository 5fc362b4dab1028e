"""A run whose timed path is broken underneath reads ``correct: false``.

Each test drives a whole run on the CPU at a small size, past the look for
a chip, with one fault planted in what the window drives: a stale answer,
an AWAC phase that leaves the matching as it found it, half of the
instance's edges left out, an answer altered after it was produced, and (on
four host devices) the grid's exchange left out.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from chipbench import run
from chipbench.tests import _tiny


@pytest.fixture
def bench(tmp_path):
    return _tiny.bench_root(tmp_path)


def test_sound_run_is_correct(bench):
    line = _tiny.run_cell(*bench)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 1 and line["failed"] == 0


def test_stale_answer_fails(bench, monkeypatch):
    real = run.route_solve
    last = []

    def stale(problem, options, warm_start=None):
        out = last[-1] if last else real(problem, options, warm_start)
        last.append(real(problem, options, warm_start))
        return out

    monkeypatch.setattr(run, "route_solve", stale)
    line = _tiny.run_cell(*bench)
    assert line["correct"] is False
    assert line["checks"]["weight_bits_off"]["value"] > 0


def test_awac_leaving_state_unchanged_fails(bench, monkeypatch):
    from repro.core import single

    def no_rounds(row, col, val, row_ptr, n, state, *args, **kwargs):
        return state, np.int32(0)

    monkeypatch.setattr(single, "_awac_loop", no_rounds)
    line = _tiny.run_cell(*bench)
    assert line["correct"] is False
    assert line["checks"]["rounds_off"]["value"] > 0


def test_half_the_edges_left_out_fails(bench, monkeypatch):
    real = run.route_solve
    calls = []

    def half(problem, options, warm_start=None):
        calls.append(1)
        if len(calls) == 1:  # the warm-up solve; the window's are broken
            return real(problem, options, warm_start)
        row, col, val = (np.array(a) for a in (problem.row, problem.col,
                                               problem.val))
        cut = int((row < problem.n).sum()) // 2
        row[cut:], col[cut:], val[cut:] = problem.n, problem.n, 0.0
        return real(dataclasses.replace(problem, row=row, col=col, val=val),
                    options, warm_start)

    monkeypatch.setattr(run, "route_solve", half)
    line = _tiny.run_cell(*bench)
    assert line["correct"] is False
    assert line["failed"] > 0


def test_altered_answer_fails(bench, monkeypatch):
    real = run.route_solve

    def altered(problem, options, warm_start=None):
        result = real(problem, options, warm_start)
        mate_row = np.array(result.mate_row)
        mate_row[[0, 1]] = mate_row[[1, 0]]
        return dataclasses.replace(result, mate_row=mate_row)

    monkeypatch.setattr(run, "route_solve", altered)
    line = _tiny.run_cell(*bench)
    assert line["correct"] is False
    assert line["checks"]["mates_off"]["value"] > 0


_GRID_RUN = textwrap.dedent("""
    import json, pathlib, sys
    import jax
    from chipbench.tests import _tiny
    if sys.argv[1] == "drop":
        jax.lax.all_to_all = lambda x, *a, **k: x  # each chip keeps its own
    root, bench_dir = _tiny.bench_root(pathlib.Path(sys.argv[2]))
    line = _tiny.run_cell(root, bench_dir, _tiny.GRID)
    print(json.dumps(line))
""")


@pytest.mark.parametrize("mode,correct", [("sound", True), ("drop", False)])
def test_grid_exchange_left_out_fails(tmp_path, mode, correct):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(_tiny.REPO),
                                           str(_tiny.REPO / "src")]))
    p = subprocess.run([sys.executable, "-c", _GRID_RUN, mode,
                        str(tmp_path / "root")],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = __import__("json").loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert line["correct"] is correct, line["checks"]
