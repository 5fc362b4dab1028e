"""The control: the plain reference put in the program's place, computed in
bfloat16, the precision below the float32 the configuration states. Its run
has to read ``correct: false``; the same reference in float32 in the
program's place reads ``correct: true``, so the comparison fails the
precision and not everything."""
import ml_dtypes
import numpy as np
import pytest

from chipbench import control, run
from chipbench.tests import _tiny


@pytest.mark.parametrize("dtype,correct", [(np.float32, True),
                                           (ml_dtypes.bfloat16, False)])
def test_control_in_the_programs_place(tmp_path, monkeypatch, dtype, correct):
    root, bench_dir = _tiny.bench_root(tmp_path, n=1024)
    monkeypatch.setattr(run, "route_solve", control.reference_route(dtype))
    line = _tiny.run_cell(root, bench_dir, seconds=0.2)
    assert line["correct"] is correct, line["checks"]
    if not correct:
        assert line["checks"]["mates_off"]["value"] > 0
