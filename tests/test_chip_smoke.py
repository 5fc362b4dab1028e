"""``chip_smoke.py`` driven at tiny size on the CPU, and the TPU routing
rules it relies on, checked with ``jax.default_backend`` patched to "tpu".

Nothing here touches a chip: the phases run on the CPU backend, and the
script's own entry point must refuse to run without a TPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import run_with_devices
from repro.core import (BackendUnavailableError, MatchingProblem,
                        SolveOptions, graph, single)
from repro.kernels import dispatch as kdispatch
from repro.runtime import chaos
from repro.runtime.resilient import _build_rungs, _classify, resilient_solve

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

# --------------------------------------------------------------------------
# the script's phases at tiny size
# --------------------------------------------------------------------------


def test_device_phase_refuses_the_cpu():
    with pytest.raises(chip_smoke.PhaseError, match="no tpu device"):
        chip_smoke.phase_device(1)
    info = chip_smoke.phase_device(1, platform="cpu")
    assert info == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                    "count": len(jax.devices())}


@pytest.mark.parametrize("kind", graph.SUITE_KINDS)
def test_oracle_phase(kind, capsys):
    chip_smoke.phase_oracle(n=48, degree=6.0, kinds=(kind,))
    out = capsys.readouterr().out
    assert "ran_interpreted=False" in out
    assert f"{kind} n=48" in out and "equal to the CPU run and to the " \
        "AWAC-phase oracle awac_parallel_rule" in out


def test_main_phase(capsys):
    chip_smoke.phase_main(n=2048, degree=8.0)
    out = capsys.readouterr().out
    assert "backend='xla'" in out and "ok: perfect" in out


def test_serving_phase(capsys):
    chip_smoke.phase_serving(sizes=(48, 96), requests=8, users=2,
                             degree=5.0)
    assert "ok: 8 requests" in capsys.readouterr().out


def test_grid_phase_on_four_fake_devices():
    out = run_with_devices(
        f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
        "import chip_smoke\n"
        "chip_smoke.phase_device(4, platform='cpu')\n"
        "chip_smoke.phase_grid(n=512, degree=8.0)\n", 4)
    assert "2x2 grid bit-identical to one chip" in out
    assert out.count("[e grid] device ") == 4


@pytest.fixture
def cache_env(monkeypatch, tmp_path):
    """``main()`` turns on the compilation cache; with the variable set it
    leaves this process's jax config alone."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_main_exits_nonzero_without_a_tpu(cache_env, capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_script_alone_exits_nonzero(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, env=env,
                          timeout=120)
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert not lines or not lines[-1].startswith("{")


def test_last_line_is_the_device_json(cache_env, monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "phase_device",
                        lambda chips: {"platform": "tpu",
                                       "kind": "TPU v5 lite", "count": 1})
    for name in ("phase_oracle", "phase_main", "phase_serving"):
        monkeypatch.setattr(chip_smoke, name, lambda **kw: None)
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_a_failed_phase_fails_the_run(cache_env, monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "phase_device",
                        lambda chips: {"platform": "tpu", "kind": "x",
                                       "count": 1})

    def broken(**kw):
        raise chip_smoke.PhaseError("wrong mates")

    monkeypatch.setattr(chip_smoke, "phase_oracle", broken)
    for name in ("phase_main", "phase_serving"):
        monkeypatch.setattr(chip_smoke, name, lambda **kw: None)
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out
    assert "FAILED" in out and '"ok"' not in out


# --------------------------------------------------------------------------
# routing on a TPU: never a Pallas backend Mosaic refuses
# --------------------------------------------------------------------------


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_auto_resolves_to_xla_on_tpu(on_tpu, tmp_path, monkeypatch):
    assert single.resolve_backend_source("auto", n=4096) == \
        ("xla", "heuristic")
    # a measured Pallas winner cannot be taken where it cannot compile
    p = tmp_path / "t.json"
    kdispatch.save_table(
        {"tpu/single_large": {"winner": "pallas_persistent",
                              "us_per_iter": {"pallas_persistent": 1.0}},
         "tpu/batched_large": {"winner": "reference",
                               "us_per_iter": {"reference": 1.0}}}, {}, p)
    monkeypatch.setenv(kdispatch.TABLE_ENV_VAR, str(p))
    kdispatch.clear_cache()
    try:
        assert single.resolve_backend("auto", n=4096) == "xla"
        assert single.resolve_backend_source("auto", n=4096, batch=8) == \
            ("reference", "table")
    finally:
        kdispatch.clear_cache()


@pytest.mark.parametrize("backend", ["pallas", "pallas_persistent"])
def test_explicit_pallas_raises_typed_error_on_tpu(on_tpu, backend):
    with pytest.raises(BackendUnavailableError, match="Mosaic"):
        SolveOptions(backend=backend)
    # the engines refuse before anything is lowered
    g = graph.generate(16, avg_degree=3.0, seed=0)
    row, col, val = (jnp.asarray(x) for x in (g.row, g.col, g.val))
    st = single.empty_state(g.n)
    with pytest.raises(BackendUnavailableError, match=backend):
        single.awac(row, col, val, g.n, st, backend=backend)


def test_resilient_chain_has_no_pallas_rung_on_tpu(on_tpu):
    for opts in (SolveOptions(), SolveOptions(backend="xla"),
                 SolveOptions(backend="reference")):
        labels = [lbl for lbl, _ in _build_rungs(opts)]
        assert labels and not any("pallas" in lbl for lbl in labels), labels
    assert [lbl for lbl, _ in _build_rungs(SolveOptions())] == \
        ["local xla", "local reference"]


def test_resilient_chain_unchanged_on_cpu():
    labels = [lbl for lbl, _ in
              _build_rungs(SolveOptions(backend="pallas_persistent"))]
    assert labels == ["local pallas_persistent", "local pallas",
                      "local xla", "local reference"]


def test_compiler_refusal_is_not_transient():
    assert _classify(NotImplementedError("Only 2D gather is supported")) \
        == "unsupported"
    # classified on the exception's type, never on its text
    assert _classify(RuntimeError("Mosaic: device preempted")) \
        == "transient"
    assert _classify(RuntimeError("device preempted")) == "transient"
    problem = graph.generate(16, avg_degree=4.0, seed=0)
    with chaos.failing_backend("pallas_persistent", "pallas",
                               exc_type=NotImplementedError) as state:
        rr = resilient_solve(MatchingProblem.from_graph(problem),
                             SolveOptions(backend="pallas_persistent"))
    assert state["n"] == 2  # one attempt per refused rung, no retries
    assert [a.outcome for a in rr.report.attempts] == \
        ["unsupported", "unsupported", "ok"]
    assert rr.report.backend_used == "local xla" and rr.report.degraded
    assert bool(np.asarray(rr.result.perfect))


def test_harness_defaults_skip_what_a_tpu_cannot_run(on_tpu, capsys):
    from benchmarks import bench_kernels
    from repro.experiments import paper_eval

    assert paper_eval.default_backends() == ("reference", "xla")
    assert bench_kernels._backends() == ("reference", "xla")
    # a grid beyond the attached devices is skipped, never a child process
    grid = (jax.device_count() + 1, 1)
    assert paper_eval._eval_grid([], {}, grid, 0, {}) == []
    assert f"skip grid {grid[0]}x1" in capsys.readouterr().out


def test_harness_defaults_unchanged_on_cpu():
    from benchmarks import bench_kernels
    from repro.experiments import paper_eval

    assert paper_eval.default_backends() == paper_eval.LOCAL_BACKENDS
    assert bench_kernels._backends() == kdispatch.MEASURED_BACKENDS
