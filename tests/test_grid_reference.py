"""The 2x2 grid route against the benchmark's plain reference.

``api.solve`` with ``grid=(2, 2)`` on four host devices, on the benchmark
generator's instances (``chipbench/gen.py``) and three links of a drift
chain, gives ``chipbench/reference.py``'s matching exactly: mates, AWAC
rounds and weight bits. That is the comparison that decides the grid
cell's ``correct`` on the chip.
"""
import json

import pytest
from _subproc import REPO, run_with_devices

NS = (256, 1024)
LINKS = 3

SCRIPT = f"""
import json, sys
sys.path.insert(0, {str(REPO)!r})
import numpy as np
from chipbench import gen, reference
from repro.core import api, dist

mesh = dist.make_mesh((2, 2))
out = {{}}
for n in {NS!r}:
    g = gen.generate(n, 16.0, "uniform", seed=2**31 + 11,
                     capacity=n * 16)
    chain = gen.drift_chain(g, {LINKS}, np.random.default_rng((n, 1)), 0.02)
    for k, val in enumerate(chain):
        got = api.solve(api.MatchingProblem(row=g.row, col=g.col, val=val,
                                            n=n),
                        api.SolveOptions(grid=mesh))
        ref = reference.solve(g.row, g.col, val, n)
        out[f"{{n}}-{{k}}"] = {{
            "mates_off": int((np.asarray(got.mate_row)[:n]
                              != ref.mate_row[:n]).sum()),
            "rounds": [int(got.awac_iters), ref.awac_rounds],
            "weight_bits": [np.asarray(got.weight, np.float32).tobytes().hex(),
                            np.float32(ref.weight).tobytes().hex()],
            "perfect": bool(got.perfect)}}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def grid_vs_reference():
    return json.loads(run_with_devices(SCRIPT, 4).strip().splitlines()[-1])


@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("link", range(LINKS))
def test_grid_equals_the_reference(grid_vs_reference, n, link):
    case = grid_vs_reference[f"{n}-{link}"]
    assert case["perfect"]
    assert case["mates_off"] == 0, case
    assert case["rounds"][0] == case["rounds"][1], case
    assert case["weight_bits"][0] == case["weight_bits"][1], case
