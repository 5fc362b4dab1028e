"""Single-device AWPM vs numpy oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph, ref, single

KINDS = ["uniform", "circuit", "antigreedy", "banded", "powerlaw"]


def _setup(n=48, deg=5.0, kind="uniform", seed=0):
    g = graph.generate(n, avg_degree=deg, kind=kind, seed=seed)
    dense = g.to_dense().astype(np.float32)
    struct = g.structure_dense()
    arrs = (jnp.asarray(g.row), jnp.asarray(g.col), jnp.asarray(g.val))
    return g, dense, struct, arrs


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_awac_matches_parallel_rule_oracle(kind, seed):
    g, dense, struct, (row, col, val) = _setup(kind=kind, seed=seed)
    mr0, mc0 = ref.greedy_maximal(dense, struct)
    mr1, mc1 = ref.mcm_kuhn(dense, struct, mr0, mc0)
    mrR, mcR, _ = ref.awac_parallel_rule(dense, struct, mr1.copy(), mc1.copy())

    st = single.state_from_mates(row, col, val, g.n, mr1, mc1)
    stJ, _ = single.awac(row, col, val, g.n, st, max_iter=500)
    assert np.array_equal(np.array(stJ.mate_row[: g.n]), mrR)


@pytest.mark.parametrize("kind", KINDS)
def test_full_pipeline_valid_perfect_and_two_thirds(kind):
    g, dense, struct, (row, col, val) = _setup(kind=kind, seed=11)
    st, iters = single.awpm(row, col, val, g.n)
    mr = np.array(st.mate_row[: g.n])
    ref.check_matching(struct, mr)
    assert ref.is_perfect(mr, g.n)
    w = float(single.matching_weight(st, g.n))
    assert abs(w - ref.matching_weight(dense, mr)) < 1e-3
    _, opt = ref.exact_mwpm(dense, struct)
    assert w >= (2.0 / 3.0) * opt - 1e-4
    # termination invariant: no augmenting 4-cycle remains
    mc = np.array(st.mate_col[: g.n])
    assert ref.find_augmenting_4cycle(dense, struct, mr, mc) is None


def test_greedy_maximal_is_maximal():
    g, dense, struct, (row, col, val) = _setup(seed=3)
    st = single.greedy_maximal(row, col, val, g.n)
    mr = np.array(st.mate_row[: g.n])
    mc = np.array(st.mate_col[: g.n])
    rr, cc = np.nonzero(struct)
    both_free = (mc[rr] == g.n) & (mr[cc] == g.n)
    assert not both_free.any(), "greedy matching is not maximal"


def test_greedy_weight_at_least_half_of_max_weight_matching():
    # greedy maximal by weight is a 1/2-approx of max-weight matching
    g, dense, struct, (row, col, val) = _setup(seed=4)
    mrg, _ = ref.greedy_maximal(dense, struct)
    w = ref.matching_weight(dense, mrg)
    _, opt = ref.exact_mwpm(dense, struct)
    assert w >= 0.5 * opt - 1e-5


@pytest.mark.parametrize("seed", [0, 5, 9])
def test_mcm_reaches_perfect(seed):
    g, dense, struct, (row, col, val) = _setup(seed=seed, deg=3.0)
    st0 = single.greedy_maximal(row, col, val, g.n)
    st = single.mcm(row, col, val, g.n, st0.mate_row, st0.mate_col)
    assert bool(single.is_perfect(st, g.n))
    mr = np.array(st.mate_row[: g.n])
    ref.check_matching(struct, mr)


def test_mcm_maximum_on_deficient_graph():
    # graph WITHOUT a guaranteed perfect matching: cardinality must equal
    # the true maximum (Kuhn's reference)
    rng = np.random.default_rng(0)
    n, m = 30, 60
    rr = rng.integers(0, n, m).astype(np.int32)
    cc = rng.integers(0, n, m).astype(np.int32)
    vv = rng.uniform(0.1, 1.0, m).astype(np.float32)
    key = rr.astype(np.int64) * n + cc
    _, idx = np.unique(key, return_index=True)
    rr, cc, vv = rr[idx], cc[idx], vv[idx]
    g = graph.from_coo(rr, cc, vv, n)
    dense = g.to_dense().astype(np.float32)
    struct = g.structure_dense()
    mrK, _ = ref.mcm_kuhn(dense, struct)
    card_ref = int((mrK < n).sum())
    st0 = single.greedy_maximal(jnp.asarray(g.row), jnp.asarray(g.col), jnp.asarray(g.val), n)
    st = single.mcm(jnp.asarray(g.row), jnp.asarray(g.col), jnp.asarray(g.val), n,
                    st0.mate_row, st0.mate_col)
    card = int((np.array(st.mate_row[:n]) < n).sum())
    assert card == card_ref
    ref.check_matching(struct, np.array(st.mate_row[:n]))


def test_state_from_mates_uv():
    g, dense, struct, (row, col, val) = _setup(seed=6)
    mr, mc = ref.greedy_maximal(dense, struct)
    mr, mc = ref.mcm_kuhn(dense, struct, mr, mc)
    st = single.state_from_mates(row, col, val, g.n, mr, mc)
    u = np.array(st.u[: g.n])
    v = np.array(st.v[: g.n])
    ii = np.arange(g.n)
    np.testing.assert_allclose(u, dense[ii, mc[ii]], rtol=1e-6)
    np.testing.assert_allclose(v, dense[mr[ii], ii], rtol=1e-6)


def test_awac_weight_monotone_nondecreasing():
    g, dense, struct, (row, col, val) = _setup(seed=8, kind="antigreedy")
    mr, mc = ref.greedy_maximal(dense, struct)
    mr, mc = ref.mcm_kuhn(dense, struct, mr, mc)
    st = single.state_from_mates(row, col, val, g.n, mr, mc)
    w0 = float(single.matching_weight(st, g.n))
    stJ, _ = single.awac(row, col, val, g.n, st)
    assert float(single.matching_weight(stJ, g.n)) >= w0 - 1e-5


@pytest.mark.parametrize("m", [0, 1, 2, 7, 64, 1000])
def test_ordered_sum_is_one_fixed_order(m):
    """The engine's weight sum is the numpy oracle's bit for bit, and a
    batch row sums as the lone vector does."""
    x = np.random.default_rng(m).random(m, dtype=np.float32) * 100
    one = np.asarray(single.ordered_sum(jnp.asarray(x)))
    rows = np.asarray(single.ordered_sum(jnp.asarray(np.stack([x, x[::-1]]))))
    assert one.tobytes() == ref.ordered_sum(x).tobytes()
    assert rows[0].tobytes() == one.tobytes()
    assert rows[1].tobytes() == ref.ordered_sum(x[::-1]).tobytes()
