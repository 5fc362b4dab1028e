"""The plain reference gives the engine's answer bit for bit: mates, AWAC
rounds and weight, on every structure family and on drifted values."""
import numpy as np
import pytest

from chipbench import gen, reference
from repro.core import graph
from repro.core.api import MatchingProblem, SolveOptions, solve


@pytest.mark.parametrize("kind", graph.SUITE_KINDS)
@pytest.mark.parametrize("n,degree,seed", [(200, 6.0, 1), (1024, 16.0, 2**31 + 17)])
def test_reference_is_the_engine(kind, n, degree, seed):
    g = gen.generate(n, degree, kind, seed=seed)
    link = gen.drift_chain(g, 2, np.random.default_rng(seed), 0.02)[1]
    for val in (g.val, link):
        got = solve(MatchingProblem(row=g.row, col=g.col, val=val, n=n),
                    SolveOptions(backend="auto"))
        ref = reference.solve(g.row, g.col, val, n)
        np.testing.assert_array_equal(np.asarray(got.mate_row), ref.mate_row)
        assert int(got.awac_iters) == ref.awac_rounds
        assert np.asarray(got.weight).tobytes() == \
            np.float32(ref.weight).tobytes()


@pytest.mark.parametrize("kind", ["uniform", "banded", "antigreedy"])
def test_warm_reference_is_the_engines_warm_path(kind):
    """Seeded with the previous link's matching, and with a seed that lost
    pairs (repair and the MCM top-up then do work), the reference gives the
    engine's warm answer."""
    n = 512
    g = gen.generate(n, 16.0, kind, seed=2**31 + 5)
    chain = gen.drift_chain(g, 3, np.random.default_rng(4), 0.05)
    opts = SolveOptions(backend="auto")
    prev = solve(MatchingProblem(row=g.row, col=g.col, val=chain[0], n=n),
                 opts)
    seeds = [(np.asarray(prev.mate_row), np.asarray(prev.mate_col))]
    broken_row, broken_col = (a.copy() for a in seeds[0])
    broken_row[:n // 4] = np.roll(broken_row[:n // 4], 1)  # not mutual
    broken_col[n // 2:n // 2 + 8] = n  # one-sided
    seeds.append((broken_row, broken_col))
    for warm in seeds:
        for val in chain[1:]:
            got = solve(MatchingProblem(row=g.row, col=g.col, val=val, n=n),
                        opts, warm_start=warm)
            ref = reference.solve(g.row, g.col, val, n, warm=warm)
            np.testing.assert_array_equal(np.asarray(got.mate_row),
                                          ref.mate_row)
            assert int(got.awac_iters) == ref.awac_rounds
            assert np.asarray(got.weight).tobytes() == \
                np.float32(ref.weight).tobytes()


def test_ordered_sum_is_pairwise():
    x = np.array([1e8, 1.0, -1e8, 1.0, 3.0], np.float32)
    # ((1e8 + 1) + (-1e8 + 1)) + (3 + 0): the ones vanish into 1e8
    assert reference.ordered_sum(x) == np.float32(3.0)
