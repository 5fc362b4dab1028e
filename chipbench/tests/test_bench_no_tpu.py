"""The command fails, and prints no result, without a TPU, and in a
directory that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

import pytest

from chipbench.tests import _tiny

ARGS = ["--workload", _tiny.ONE, "--seed", "2147483659", "--seconds", "1",
        "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run(_tiny.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(_tiny.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(_tiny.BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("kind", ["TPU v4", "cpu"])
def test_unknown_device_kind_is_an_error(kind):
    from chipbench import run

    with pytest.raises(run.BenchError, match="no peaks"):
        run.peaks_for(run.BENCH_DIR, kind)
