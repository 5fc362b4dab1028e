"""Differential harness for the batched AWPM engine (DESIGN.md §4).

Contract under test: for every instance b and every backend,
``batch.awpm_batched(row, col, val, n)`` returns exactly the state and
iteration count ``single.awpm(row[b], col[b], val[b], n)`` would — including
batches whose instances converge at very different speeds (one in 1 AWAC
iteration, another in ~20), where the per-instance masks must freeze early
finishers bit-exactly while the rest keep iterating.

The "grid1x1" case runs the same batches through ``solve`` on the grid
engine over an in-process 1x1 mesh (its "fused" sweep) and holds them to
the single-instance "xla" engine: mates and AWAC rounds (the facade's
result carries no duals).

Also covers degenerate inputs (n=1, all-ties weights, single dense row) and
error paths (unknown backend, explicit window_steps / precomputed row_ptr
overrides) that previously had zero coverage.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MatchingProblem, SolveOptions, batch, graph, single
from repro.core.single import MatchState
from repro.sparse.csr import batched_row_ptr_from_sorted, row_ptr_from_sorted

BACKENDS = ["reference", "xla", "grid1x1"]


def _local(backend):
    """The single-instance engine a batched route is held to."""
    return "xla" if backend == "grid1x1" else backend


def _awpm_batched(row, col, val, n, backend):
    """``batch.awpm_batched`` on a local backend; for "grid1x1", ``solve``
    on the grid engine over a 1x1 mesh. Returns (state, iters [B]); the
    grid's state carries mates only (duals None)."""
    if backend != "grid1x1":
        return batch.awpm_batched(row, col, val, n, backend=backend)
    return _solve_grid(MatchingProblem(row=row, col=col, val=val, n=n))


def _awac_batched(row, col, val, n, state, backend):
    """``batch.awac_batched`` from ``state``; for "grid1x1", the grid
    engine warm-started from the state's mates (their duals are rebuilt
    from the mates, as ``state`` holds them)."""
    if backend != "grid1x1":
        return batch.awac_batched(row, col, val, n, state, backend=backend)
    return _solve_grid(MatchingProblem(row=row, col=col, val=val, n=n),
                       warm_start=(state.mate_row, state.mate_col))


def _solve_grid(problem, warm_start=None):
    from repro.core import solve
    from repro.core.dist import make_mesh

    res = solve(problem, SolveOptions(grid=make_mesh((1, 1))),
                warm_start=warm_start)
    assert res.execution.backend == "fused"
    return MatchState(res.mate_row, res.mate_col, None, None), \
        res.awac_iters


def _assert_instance_identical(stS, itS, stB, itB, i, msg):
    assert int(itS) == int(itB[i]), f"{msg}: iters {int(itS)} != {int(itB[i])}"
    names = ["mate_row", "mate_col", "u", "v"]
    for nm, a, b in zip(names, stS, stB):
        if b is not None:
            np.testing.assert_array_equal(np.array(a), np.array(b[i]),
                                          err_msg=f"{msg}: {nm}")


def _heterogeneous_batch(n=48):
    kinds = [("uniform", 0), ("antigreedy", 11), ("circuit", 2),
             ("banded", 3), ("powerlaw", 5)]
    gs = [graph.generate(n, avg_degree=5.0 + (i % 3), kind=k, seed=s)
          for i, (k, s) in enumerate(kinds)]
    return gs, batch.stack_graphs(gs)


@pytest.mark.parametrize("backend", BACKENDS)
def test_awpm_batched_bit_identical_per_instance(backend):
    n = 48
    gs, (row, col, val) = _heterogeneous_batch(n)
    stB, itB = _awpm_batched(row, col, val, n, backend)
    assert bool(batch.is_perfect_batched(stB, n).all())
    for i in range(len(gs)):
        stS, itS = single.awpm(row[i], col[i], val[i], n,
                               backend=_local(backend))
        _assert_instance_identical(stS, itS, stB, itB, i,
                                   f"{backend}/instance{i}")


def _chain_graph(n):
    """Overlapping heavy 4-cycles: from the diagonal matching, AWAC's
    vertex-disjointness + deterministic fallback force ~n/2 sequential
    augmentation rounds — the slow-convergence extreme."""
    rows, cols, vals = [], [], []
    for i in range(n):
        rows.append(i), cols.append(i), vals.append(0.1)
    for i in range(n - 1):
        w = 0.5 + 0.4 * i / n
        rows += [i, i + 1]
        cols += [i + 1, i]
        vals += [w, w]
    return graph.from_coo(np.array(rows, np.int32), np.array(cols, np.int32),
                          np.array(vals, np.float32), n)


@pytest.mark.parametrize("backend", BACKENDS)
def test_mixed_convergence_speeds_within_batch(backend):
    """One instance needs ~20 AWAC iterations, the other converges in 1:
    the early finisher's state must stay frozen (bit-exact) while the slow
    instance keeps augmenting."""
    n = 40
    slow = _chain_graph(n)  # ~n/2 iterations from the diagonal matching
    fast = graph.generate(n, avg_degree=3.0, kind="circuit", seed=2)
    gs = [slow, fast]
    row, col, val = batch.stack_graphs(gs)

    # per-instance initial states: diagonal matching for the chain, greedy +
    # MCM for the circuit instance (its usual pipeline entry into AWAC)
    st_slow = single.state_from_mates(row[0], col[0], val[0], n,
                                      np.arange(n), np.arange(n))
    st0 = single.greedy_maximal(row[1], col[1], val[1], n)
    st_fast = single.mcm(row[1], col[1], val[1], n, st0.mate_row,
                         st0.mate_col)
    stacked = MatchState(*(jnp.stack([a, b]) for a, b in
                           zip(st_slow, st_fast)))

    stB, itB = _awac_batched(row, col, val, n, stacked, backend)
    its = []
    for i, st_i in enumerate((st_slow, st_fast)):
        stS, itS = single.awac(row[i], col[i], val[i], n, st_i,
                               backend=_local(backend))
        _assert_instance_identical(stS, itS, stB, itB, i,
                                   f"{backend}/mixed{i}")
        its.append(int(itS))
    assert its[0] >= 20 and its[1] <= 2, its  # genuinely mixed speeds


# --------------------------------------------------------------------------
# degenerate inputs
# --------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_degenerate_n1(backend):
    g = graph.from_coo(np.array([0]), np.array([0]),
                       np.array([0.7], np.float32), 1)
    row, col, val = batch.stack_graphs([g, g])
    stB, itB = _awpm_batched(row, col, val, 1, backend)
    assert bool(batch.is_perfect_batched(stB, 1).all())
    np.testing.assert_array_equal(np.array(stB.mate_row[:, 0]), [0, 0])
    stS, itS = single.awpm(row[0], col[0], val[0], 1,
                           backend=_local(backend))
    _assert_instance_identical(stS, itS, stB, itB, 0, f"{backend}/n1")


@pytest.mark.parametrize("backend", BACKENDS)
def test_degenerate_all_ties(backend):
    """Every weight equal: every gain ties at 0 and the smallest-row/
    smallest-payload tie-breaks are all that decide — must agree with the
    sequential engine everywhere."""
    n = 24
    gs = []
    for seed in (0, 1):
        g0 = graph.generate(n, avg_degree=4.0, kind="uniform", seed=seed,
                            normalize=False)
        real = np.asarray(g0.row) < n
        gs.append(graph.from_coo(np.asarray(g0.row)[real],
                                 np.asarray(g0.col)[real],
                                 np.full(int(real.sum()), 0.5, np.float32),
                                 n))
    row, col, val = batch.stack_graphs(gs)
    stB, itB = _awpm_batched(row, col, val, n, backend)
    for i in range(len(gs)):
        stS, itS = single.awpm(row[i], col[i], val[i], n,
                               backend=_local(backend))
        _assert_instance_identical(stS, itS, stB, itB, i,
                                   f"{backend}/ties{i}")


@pytest.mark.parametrize("backend", BACKENDS)
def test_degenerate_single_dense_row(backend):
    """One row holds n entries (the widest possible CSR window) next to an
    instance with ordinary degrees — the shared window_steps must cover
    both."""
    n = 16
    rng = np.random.default_rng(3)
    rows = np.concatenate([np.zeros(n, np.int32), np.arange(1, n, dtype=np.int32)])
    cols = np.concatenate([np.arange(n, dtype=np.int32),
                           rng.permutation(n - 1).astype(np.int32)])
    vals = rng.uniform(0.1, 1.0, rows.shape[0]).astype(np.float32)
    dense_row = graph.from_coo(rows, cols, vals, n)
    sparse = graph.generate(n, avg_degree=3.0, kind="uniform", seed=1)
    row, col, val = batch.stack_graphs([dense_row, sparse])
    stB, itB = _awpm_batched(row, col, val, n, backend)
    for i in range(2):
        stS, itS = single.awpm(row[i], col[i], val[i], n,
                               backend=_local(backend))
        _assert_instance_identical(stS, itS, stB, itB, i,
                                   f"{backend}/dense{i}")


# --------------------------------------------------------------------------
# error paths and explicit overrides
# --------------------------------------------------------------------------


def test_unknown_backend_raises():
    n = 8
    g = graph.generate(n, avg_degree=3.0, seed=0)
    row, col, val = batch.stack_graphs([g])
    st = single.empty_state(n)
    with pytest.raises(ValueError, match="unknown AWAC backend"):
        single.awac(jnp.asarray(g.row), jnp.asarray(g.col),
                    jnp.asarray(g.val), n, st, backend="bogus")
    stacked = MatchState(*(x[None] for x in st))
    with pytest.raises(ValueError, match="unknown AWAC backend"):
        batch.awac_batched(row, col, val, n, stacked, backend="bogus")
    with pytest.raises(ValueError, match="unknown AWAC backend"):
        batch.awpm_batched(row, col, val, n, backend="bogus")


def test_resolve_backend_passthrough_and_auto():
    assert single.resolve_backend("reference") == "reference"
    assert single.resolve_backend("xla") == "xla"
    assert single.resolve_backend("auto") == "xla"


def test_explicit_window_steps_and_row_ptr_overrides():
    """Precomputed row_ptr and an oversized explicit window depth must not
    change any result (extra binary-search rounds are no-ops)."""
    n = 32
    gs = [graph.generate(n, avg_degree=5.0, kind=k, seed=s)
          for k, s in (("uniform", 0), ("antigreedy", 4))]
    row, col, val = batch.stack_graphs(gs)
    rp = batched_row_ptr_from_sorted(row, n)
    st0, it0 = batch.awpm_batched(row, col, val, n, backend="xla")
    st1, it1 = batch.awpm_batched(row, col, val, n, backend="xla",
                                  row_ptr=rp, window_steps=32)
    np.testing.assert_array_equal(np.array(it0), np.array(it1))
    for a, b in zip(st0, st1):
        np.testing.assert_array_equal(np.array(a), np.array(b))
    # same override contract on the sequential engine
    g = gs[0]
    r, c, v = jnp.asarray(g.row), jnp.asarray(g.col), jnp.asarray(g.val)
    sp = row_ptr_from_sorted(r, n)
    sA, iA = single.awpm(r, c, v, n, backend="xla")
    stg = single.greedy_maximal(r, c, v, n)
    stg = single.mcm(r, c, v, n, stg.mate_row, stg.mate_col)
    sB, iB = single.awac(r, c, v, n, stg, backend="xla", row_ptr=sp,
                         window_steps=32)
    assert int(iA) == int(iB)
    for a, b in zip(sA, sB):
        np.testing.assert_array_equal(np.array(a), np.array(b))


def test_max_iter_zero_runs_no_awac_iterations():
    """max_iter=0 must admit no AWAC iteration in either engine (the
    batched loop's initial active mask honors the bound)."""
    n = 16
    g = graph.generate(n, avg_degree=4.0, kind="antigreedy", seed=0)
    row, col, val = batch.stack_graphs([g, g])
    st = single.greedy_maximal(row[0], col[0], val[0], n)
    st = single.mcm(row[0], col[0], val[0], n, st.mate_row, st.mate_col)
    stacked = MatchState(*(jnp.stack([a, a]) for a in st))
    sB, iB = batch.awac_batched(row, col, val, n, stacked, max_iter=0)
    sS, iS = single.awac(row[0], col[0], val[0], n, st, max_iter=0)
    assert int(iS) == 0
    for i in range(2):
        _assert_instance_identical(sS, iS, sB, iB, i, f"max_iter0/{i}")


def test_stack_graphs_rejects_mixed_n():
    g1 = graph.generate(8, avg_degree=3.0, seed=0)
    g2 = graph.generate(9, avg_degree=3.0, seed=0)
    with pytest.raises(ValueError, match="share n"):
        batch.stack_graphs([g1, g2])


def test_batched_pivot_metric_validation():
    from repro.core import pivot

    with pytest.raises(ValueError, match="unknown pivot metric"):
        pivot.batched_pivot_permutations([np.eye(4)], metric="bogus")
