"""The benchmark's generator copies give the program's bits."""
import numpy as np
import pytest

from chipbench import gen
from repro.core import graph
from repro.serving import loadgen


def _same(a, b):
    assert a.n == b.n and a.nnz == b.nnz
    for name in ("row", "col", "val"):
        x, y = getattr(a, name), getattr(b, name)
        assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), name


@pytest.mark.parametrize("kind", graph.SUITE_KINDS)
@pytest.mark.parametrize("n,degree,seed", [(64, 4.0, 0), (500, 16.0, 2**31 + 9)])
def test_generate_matches_program(kind, n, degree, seed):
    _same(gen.generate(n, degree, kind, seed=seed),
          graph.generate(n, degree, kind, seed=seed))


def test_generate_capacity_pads_only():
    g = gen.generate(256, 16.0, "uniform", seed=3, capacity=256 * 16)
    ref = graph.generate(256, 16.0, "uniform", seed=3)
    assert g.row.size == 256 * 16
    for name in ("row", "col", "val"):
        np.testing.assert_array_equal(getattr(g, name)[:ref.nnz],
                                      getattr(ref, name)[:ref.nnz])
    assert (g.row[ref.nnz:] == 256).all() and (g.val[ref.nnz:] == 0).all()


@pytest.mark.parametrize("kind", ["uniform", "powerlaw"])
def test_drift_chain_is_perturbed_then_renormalized(kind):
    base = graph.generate(400, 16.0, kind, seed=2**31 + 1)
    chain = gen.drift_chain(gen.Graph(base.n, base.nnz, base.row, base.col,
                                      base.val),
                            6, np.random.default_rng((7, 1)), 0.02)
    rng = np.random.default_rng((7, 1))
    g = base
    assert chain[0].tobytes() == g.val.tobytes()
    for link in chain[1:]:
        g = loadgen.perturbed(g, rng, 0.02, 0.0)
        g.val[:g.nnz] = graph.normalize_rowcol_max(g.row[:g.nnz],
                                                   g.col[:g.nnz],
                                                   g.val[:g.nnz])
        assert link.dtype == np.float32
        assert link.tobytes() == g.val.tobytes()
