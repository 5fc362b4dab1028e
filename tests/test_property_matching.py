"""Hypothesis property tests on the matching system's invariants."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import batch, graph, ref, single
from repro.core.api import MatchingProblem, SolveOptions, solve
from repro.sparse.ops import lex_searchsorted

SET = dict(max_examples=25, deadline=None)
LOCAL_BACKENDS = ("reference", "xla")


@st.composite
def planted_graph(draw, n=None):
    if n is None:
        n = draw(st.integers(8, 40))
    deg = draw(st.floats(2.0, 6.0))
    kind = draw(st.sampled_from(["uniform", "circuit", "antigreedy", "banded"]))
    seed = draw(st.integers(0, 10_000))
    return graph.generate(n, avg_degree=deg, kind=kind, seed=seed)


@st.composite
def deficient_problem(draw):
    """A planted graph with every edge of one column removed — structurally
    infeasible (that column can never be matched), with the victim column
    drawn so the deficiency is not always at the boundary."""
    g = draw(planted_graph())
    victim = draw(st.integers(0, g.n - 1))
    keep = np.asarray(g.col) != victim
    row = np.asarray(g.row)[keep]
    col = np.asarray(g.col)[keep]
    val = np.asarray(g.val)[keep]
    return MatchingProblem.from_coo(row, col, val, g.n), victim


@st.composite
def planted_batch(draw):
    """A batch of heterogeneous planted graphs (mixed kinds/degrees/seeds)
    sharing n, stacked to a common padded capacity."""
    n = draw(st.integers(8, 24))
    b = draw(st.integers(2, 4))
    return [draw(planted_graph(n=n)) for _ in range(b)]


@given(planted_graph())
@settings(**SET)
def test_awpm_perfect_valid_and_two_thirds_optimal(g):
    dense = g.to_dense().astype(np.float32)
    struct = g.structure_dense()
    st_, iters = single.awpm(jnp.asarray(g.row), jnp.asarray(g.col),
                             jnp.asarray(g.val), g.n)
    mr = np.array(st_.mate_row[: g.n])
    mc = np.array(st_.mate_col[: g.n])
    ref.check_matching(struct, mr)
    assert ref.is_perfect(mr, g.n)
    # Pettie-Sanders: no augmenting 4-cycle => >= 2/3-optimal
    assert ref.find_augmenting_4cycle(dense, struct, mr, mc) is None
    _, opt = ref.exact_mwpm(dense, struct)
    w = float(single.matching_weight(st_, g.n))
    assert w >= (2.0 / 3.0) * opt - 1e-4


@given(planted_batch())
@settings(max_examples=15, deadline=None)
def test_awpm_batched_perfect_valid_and_two_thirds_optimal(gs):
    """Every instance routed through the batched engine satisfies the same
    invariants the sequential engine guarantees: a valid perfect matching
    that admits no augmenting 4-cycle and is >= 2/3-optimal."""
    n = gs[0].n
    row, col, val = batch.stack_graphs(gs)
    stB, _ = batch.awpm_batched(row, col, val, n)
    assert bool(batch.is_perfect_batched(stB, n).all())
    weights = np.array(batch.matching_weight_batched(stB, n))
    for i, g in enumerate(gs):
        dense = g.to_dense().astype(np.float32)
        struct = g.structure_dense()
        mr = np.array(stB.mate_row[i, :n])
        mc = np.array(stB.mate_col[i, :n])
        ref.check_matching(struct, mr)
        assert ref.is_perfect(mr, n)
        assert ref.find_augmenting_4cycle(dense, struct, mr, mc) is None
        _, opt = ref.exact_mwpm(dense, struct)
        assert weights[i] >= (2.0 / 3.0) * opt - 1e-4


@given(planted_graph())
@settings(**SET)
def test_awac_round_never_decreases_weight_and_stays_perfect(g):
    dense = g.to_dense().astype(np.float32)
    struct = g.structure_dense()
    mr, mc = ref.greedy_maximal(dense, struct)
    mr, mc = ref.mcm_kuhn(dense, struct, mr, mc)
    w_prev = ref.matching_weight(dense, mr)
    for _ in range(50):
        surv, n_cand = ref.awac_round_select(dense, struct, mr, mc)
        if not surv:
            break
        mr, mc = ref.apply_cycles(mr, mc, surv)
        ref.check_matching(struct, mr)
        assert ref.is_perfect(mr, g.n)
        w = ref.matching_weight(dense, mr)
        assert w > w_prev - 1e-6
        w_prev = w


@given(planted_graph())
@settings(**SET)
def test_survivor_cycles_are_vertex_disjoint(g):
    dense = g.to_dense().astype(np.float32)
    struct = g.structure_dense()
    mr, mc = ref.greedy_maximal(dense, struct)
    mr, mc = ref.mcm_kuhn(dense, struct, mr, mc)
    surv, _ = ref.awac_round_select(dense, struct, mr, mc)
    rows, cols = set(), set()
    for i, j in surv:
        r2, c2 = mr[j], mc[i]
        for r in (i, r2):
            assert r not in rows
            rows.add(r)
        for c in (j, c2):
            assert c not in cols
            cols.add(c)


@given(deficient_problem())
@settings(max_examples=15, deadline=None)
def test_infeasible_short_circuits_consistently_across_backends(arg):
    """Structurally deficient instances must terminate promptly with
    ``perfect=False`` under ``on_invalid="degrade"`` — AWAC preserves
    cardinality, so its round budget is pure waste on an imperfect matching
    and the pipeline short-circuits after MCM (``awac_iters == 0`` even with
    an absurd ``max_iter``). The maximal matching and its sentinel slots
    must agree bit-for-bit across every local backend."""
    problem, victim = arg
    n = problem.n
    mates = {}
    for backend in LOCAL_BACKENDS:
        opts = SolveOptions(backend=backend, on_invalid="degrade",
                            max_iter=10**6)
        t0 = time.perf_counter()
        res = solve(problem, opts)
        dt = time.perf_counter() - t0
        # timing assertion: O(MCM) work, never max_iter AWAC rounds (a
        # million rounds at ~ms each would be hours, not seconds)
        assert dt < 5.0, f"{backend}: {dt:.1f}s — AWAC was not skipped?"
        assert not bool(res.perfect)
        assert int(res.awac_iters) == 0
        mr = np.asarray(res.mate_row)
        mc = np.asarray(res.mate_col)
        assert mr.shape == mc.shape == (n + 1,)
        assert mr[victim] == n  # the deficient column is unmatched
        assert res.diagnosis is not None and not res.diagnosis.solvable
        mates[backend] = (mr, mc)
        # the partial matching is still consistent: matched pairs mutual,
        # unmatched slots hold the sentinel n
        matched = np.nonzero(mr[:n] < n)[0]
        assert np.array_equal(mc[mr[matched]], matched)
    ref_mr, ref_mc = mates["reference"]
    for backend in LOCAL_BACKENDS[1:]:
        mr, mc = mates[backend]
        assert np.array_equal(mr, ref_mr), f"{backend} mate_row diverges"
        assert np.array_equal(mc, ref_mc), f"{backend} mate_col diverges"


def test_infeasible_short_circuits_on_1x1_grid():
    """The distributed route honours the same degrade short-circuit and
    produces the same sentinel mates as the local engines (1x1 grid runs
    in-process; the multi-device variant lives in tests/test_chaos.py)."""
    import jax

    g = graph.generate(16, avg_degree=4.0, kind="uniform", seed=3)
    keep = np.asarray(g.col) != 5
    problem = MatchingProblem.from_coo(np.asarray(g.row)[keep],
                                       np.asarray(g.col)[keep],
                                       np.asarray(g.val)[keep], g.n)
    local = solve(problem, SolveOptions(on_invalid="degrade", max_iter=10**6))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    dist = solve(problem, SolveOptions(grid=mesh, on_invalid="degrade",
                                       max_iter=10**6))
    assert not bool(dist.perfect) and int(dist.awac_iters) == 0
    assert np.array_equal(np.asarray(dist.mate_row),
                          np.asarray(local.mate_row))
    assert np.array_equal(np.asarray(dist.mate_col),
                          np.asarray(local.mate_col))
    assert dist.diagnosis is not None and not dist.diagnosis.solvable


@given(
    st.lists(st.tuples(st.integers(0, 30), st.integers(0, 30)), min_size=1,
             max_size=60),
    st.lists(st.tuples(st.integers(0, 31), st.integers(0, 31)), min_size=1,
             max_size=20),
)
@settings(**SET)
def test_lex_searchsorted_matches_python(pairs, queries):
    pairs = sorted(set(pairs))
    kr = jnp.array([p[0] for p in pairs], jnp.int32)
    kc = jnp.array([p[1] for p in pairs], jnp.int32)
    qr = jnp.array([q[0] for q in queries], jnp.int32)
    qc = jnp.array([q[1] for q in queries], jnp.int32)
    pos, found = lex_searchsorted(kr, kc, qr, qc)
    pset = set(pairs)
    for k, q in enumerate(queries):
        assert bool(found[k]) == (q in pset)
        if q in pset:
            assert pairs[int(pos[k])] == q
