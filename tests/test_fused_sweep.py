"""Fused AWAC sweep engine vs the seed reference — bit-identical winners,
loops and solves.

Covers the two Step-C backends on padded COO instances:
  * reference  — seed jnp path (global lex search + two-pass reductions)
  * xla        — CSR-windowed lookup + packed-key one-pass segment_max
including gain ties, the all-padding instance, and the no-candidate case;
then the whole ``single.awac`` loop (mates, duals and rounds), the batched
loop against the single one, and the grid's "fused" engine on a 1x1 mesh
against the local "xla" solve.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    MatchingProblem,
    SolveOptions,
    batch,
    graph,
    single,
    solve,
)
from repro.sparse.csr import max_row_nnz, row_ptr_from_sorted, window_depth
from repro.sparse.ops import segment_max_with_payload

KINDS = ["uniform", "circuit", "antigreedy", "banded", "powerlaw"]


def _mcm_state(g):
    row, col, val = jnp.asarray(g.row), jnp.asarray(g.col), jnp.asarray(g.val)
    st = single.greedy_maximal(row, col, val, g.n)
    st = single.mcm(row, col, val, g.n, st.mate_row, st.mate_col)
    return row, col, val, st


def _winners_all_backends(row, col, val, n, st, min_gain=1e-6):
    rp = row_ptr_from_sorted(row, n)
    ws = window_depth(max_row_nnz(row, n))
    ref = single.awac_cwinners(row, col, val, n, st, min_gain)
    xla = single.awac_cwinners_fused(row, col, val, rp, n, st, min_gain, ws)
    with jax.enable_x64(True):  # packed-key one-pass reduction branch
        xla64 = single.awac_cwinners_fused(row, col, val, rp, n, st,
                                           min_gain, ws)
    return ref, xla, xla64


def _assert_identical(ref, others, msg):
    names = ["Cgain", "Ci", "Cw1", "Cw2"]
    for tag, other in others.items():
        for nm, a, b in zip(names, ref, other):
            np.testing.assert_array_equal(
                np.array(a), np.array(b), err_msg=f"{msg}: {tag} {nm}")


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_cwinners_bit_identical_across_backends(kind, seed):
    g = graph.generate(72, avg_degree=5.0, kind=kind, seed=seed)
    row, col, val, st = _mcm_state(g)
    ref, xla, xla64 = _winners_all_backends(row, col, val, g.n, st)
    _assert_identical(ref, {"xla": xla, "xla-packed": xla64},
                      f"{kind}/{seed}")


def test_cwinners_with_gain_ties():
    # quantized weights force exact f32 gain ties across different rows of
    # the same column; the smallest-row tie-break must agree everywhere
    n = 32
    g0 = graph.generate(n, avg_degree=6.0, kind="uniform", seed=3,
                        normalize=False)
    real = np.asarray(g0.row) < n
    val = (np.round(np.asarray(g0.val)[real] * 4) / 4 + 0.25).astype(np.float32)
    g = graph.from_coo(np.asarray(g0.row)[real], np.asarray(g0.col)[real],
                       val, n)
    row, col, val, st = _mcm_state(g)
    ref, xla, xla64 = _winners_all_backends(row, col, val, g.n, st)
    _assert_identical(ref, {"xla": xla, "xla-packed": xla64},
                      "ties")


def test_cwinners_all_padding():
    n = 16
    cap = 48
    row = jnp.full((cap,), n, jnp.int32)
    col = jnp.full((cap,), n, jnp.int32)
    val = jnp.zeros((cap,), jnp.float32)
    st = single.empty_state(n)
    ref, xla, xla64 = _winners_all_backends(row, col, val, n, st)
    _assert_identical(ref, {"xla": xla, "xla-packed": xla64},
                      "all-padding")
    assert np.all(np.isneginf(np.array(ref[0])))
    assert np.all(np.array(ref[1]) == n)


def test_cwinners_no_candidates():
    # perfect diagonal matching with no off-diagonal edges: no 4-cycles
    n = 12
    row = np.arange(n, dtype=np.int32)
    col = np.arange(n, dtype=np.int32)
    val = np.linspace(0.5, 1.0, n).astype(np.float32)
    g = graph.from_coo(row, col, val, n)
    rowj, colj, valj = jnp.asarray(g.row), jnp.asarray(g.col), jnp.asarray(g.val)
    st = single.state_from_mates(rowj, colj, valj, n, np.arange(n),
                                 np.arange(n))
    ref, xla, xla64 = _winners_all_backends(rowj, colj, valj, n, st)
    _assert_identical(ref, {"xla": xla, "xla-packed": xla64},
                      "no-candidates")
    assert np.all(np.isneginf(np.array(ref[0])))


@pytest.mark.parametrize("backend", ["xla"])
def test_full_awac_loop_matches_reference(backend):
    g = graph.generate(64, avg_degree=6.0, kind="antigreedy", seed=11)
    row, col, val = jnp.asarray(g.row), jnp.asarray(g.col), jnp.asarray(g.val)
    st = single.greedy_maximal(row, col, val, g.n)
    st = single.mcm(row, col, val, g.n, st.mate_row, st.mate_col)
    sR, iR = single.awac(row, col, val, g.n, st, backend="reference")
    sB, iB = single.awac(row, col, val, g.n, st, backend=backend)
    assert int(iR) == int(iB)
    for a, b in zip(sR, sB):
        np.testing.assert_array_equal(np.array(a), np.array(b))


def test_packed_segment_max_matches_two_pass():
    rng = np.random.default_rng(4)
    m, ns = 4000, 129
    vals = jnp.asarray(np.round(rng.uniform(-1, 1, m), 2), jnp.float32)
    vals = vals.at[:50].set(-jnp.inf)  # explicit -inf entries
    pay = jnp.asarray(rng.integers(0, 1 << 20, m), jnp.int32)
    seg = jnp.asarray(rng.integers(0, ns + 1, m), jnp.int32)  # incl. dump seg
    g1, p1 = segment_max_with_payload(vals, pay, seg, ns + 1)
    with jax.enable_x64(True):
        g2, p2 = segment_max_with_payload(vals, pay, seg, ns + 1)
    np.testing.assert_array_equal(np.array(g1), np.array(g2))
    np.testing.assert_array_equal(np.array(p1), np.array(p2))


# --------------------------------------------------------------------------
# the whole AWAC loop: "xla" against "reference" (mates, duals, rounds)
# --------------------------------------------------------------------------

STATE_FIELDS = ("mate_row", "mate_col", "u", "v")


def _assert_states_equal(ref, other, msg):
    for nm, a, b in zip(STATE_FIELDS, ref, other):
        np.testing.assert_array_equal(np.array(a), np.array(b),
                                      err_msg=f"{msg}: {nm}")


def _loops_agree(row, col, val, n, st, msg, **kw):
    sR, iR = single.awac(row, col, val, n, st, backend="reference", **kw)
    sX, iX = single.awac(row, col, val, n, st, backend="xla", **kw)
    assert int(iX) == int(iR), f"{msg}: xla iters {int(iX)} != {int(iR)}"
    _assert_states_equal(sR, sX, msg)
    return sX, int(iX)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_awac_loop_bit_identical_to_reference(kind, seed):
    g = graph.generate(72, avg_degree=5.0, kind=kind, seed=seed)
    row, col, val, st = _mcm_state(g)
    _loops_agree(row, col, val, g.n, st, f"{kind}/{seed}")


def test_awac_loop_with_gain_ties_matches_reference():
    # every weight on a quarter grid: whole rounds decided by tie-breaks
    n = 48
    g0 = graph.generate(n, avg_degree=6.0, kind="antigreedy", seed=8,
                        normalize=False)
    real = np.asarray(g0.row) < n
    val = (np.round(np.asarray(g0.val)[real] * 4) / 4 + 0.25).astype(
        np.float32)
    g = graph.from_coo(np.asarray(g0.row)[real], np.asarray(g0.col)[real],
                       val, n)
    row, col, val, st = _mcm_state(g)
    _loops_agree(row, col, val, n, st, "ties")


def test_awac_loop_actually_iterates():
    # antigreedy instances force AWAC rounds, counted identically
    g = graph.generate(96, avg_degree=6.0, kind="antigreedy", seed=11)
    row, col, val, st = _mcm_state(g)
    _, iters = _loops_agree(row, col, val, g.n, st, "antigreedy")
    assert iters > 0


def test_awac_max_iter_zero_is_noop():
    g = graph.generate(40, avg_degree=5.0, kind="antigreedy", seed=3)
    row, col, val, st = _mcm_state(g)
    sX, iters = _loops_agree(row, col, val, g.n, st, "max_iter=0",
                             max_iter=0)
    assert iters == 0
    _assert_states_equal(st, sX, "max_iter=0")


def test_awac_degrade_skips_the_loop_on_an_imperfect_state():
    # the degrade-infeasible gate: an imperfect matching returns unchanged
    # with no rounds run; a perfect one runs the loop as without the gate
    g = graph.generate(32, avg_degree=5.0, kind="antigreedy", seed=5)
    row, col, val, st = _mcm_state(g)
    n = g.n
    i = int(st.mate_col[0])
    broken = single.MatchState(st.mate_row.at[i].set(n),
                               st.mate_col.at[0].set(n), st.u, st.v)
    sX, iters = _loops_agree(row, col, val, n, broken, "imperfect",
                             degrade_infeasible=True)
    assert iters == 0
    _assert_states_equal(broken, sX, "imperfect")
    sG, iG = _loops_agree(row, col, val, n, st, "gated",
                          degrade_infeasible=True)
    sU, iU = single.awac(row, col, val, n, st, backend="xla")
    assert iG == int(iU) > 0
    _assert_states_equal(sU, sG, "gate open")


def test_awac_small_capacity_matches_reference():
    # a capacity under one 128-lane tile: three edges a row, n = 12
    n = 12
    rng = np.random.default_rng(9)
    row = np.repeat(np.arange(n, dtype=np.int32), 3)
    col = np.stack([np.arange(n), (np.arange(n) + 1) % n,
                    (np.arange(n) + 5) % n], axis=1).astype(np.int32).ravel()
    val = rng.uniform(0.1, 1.0, row.size).astype(np.float32)
    g = graph.from_coo(row, col, val, n)
    assert g.capacity < 128
    rowj, colj, valj, st = _mcm_state(g)
    _loops_agree(rowj, colj, valj, n, st, "small cap")


def test_awac_batched_matches_single():
    n = 48
    kinds = [("uniform", 0), ("antigreedy", 7), ("circuit", 2), ("banded", 3)]
    graphs = [graph.generate(n, avg_degree=4.0 + (i % 3), kind=k, seed=s)
              for i, (k, s) in enumerate(kinds)]
    row, col, val = batch.stack_graphs(graphs)
    mr, mc = batch.greedy_maximal_batched(row, col, val, n)
    mr, mc = batch.mcm_batched(row, col, val, n, mr, mc)
    st = batch.state_from_mates_batched(row, col, val, n, mr, mc)
    sX, iX = batch.awac_batched(row, col, val, n, st, backend="xla")
    sR, iR = batch.awac_batched(row, col, val, n, st, backend="reference")
    np.testing.assert_array_equal(np.array(iX), np.array(iR))
    _assert_states_equal(sR, sX, "batched")
    for b in range(len(graphs)):
        st1 = single.MatchState(st.mate_row[b], st.mate_col[b], st.u[b],
                                st.v[b])
        s1, i1 = single.awac(row[b], col[b], val[b], n, st1, backend="xla")
        assert int(i1) == int(iX[b])
        _assert_states_equal(s1, [x[b] for x in sX], f"instance {b}")


# --------------------------------------------------------------------------
# the grid's "fused" engine on a 1x1 mesh against the local "xla" solve
# --------------------------------------------------------------------------


def _assert_results_identical(local, grid, msg):
    for nm in ("mate_row", "mate_col", "awac_iters", "perfect"):
        np.testing.assert_array_equal(
            np.asarray(getattr(local, nm)), np.asarray(getattr(grid, nm)),
            err_msg=f"{msg}: {nm}")
    # the weight to the bit: both routes sum in single.ordered_sum's order
    assert np.asarray(local.weight, np.float32).tobytes() == \
        np.asarray(grid.weight, np.float32).tobytes(), msg


@pytest.fixture(scope="module")
def grid_1x1():
    from repro.core.dist import make_mesh

    return make_mesh((1, 1))


@pytest.mark.parametrize("kind", KINDS)
def test_grid_fused_1x1_matches_local_xla(kind, grid_1x1):
    g = graph.generate(96, avg_degree=6.0, kind=kind, seed=KINDS.index(kind))
    p = MatchingProblem.from_graph(g)
    local = solve(p, SolveOptions(backend="xla"))
    grid = solve(p, SolveOptions(backend="fused", grid=grid_1x1))
    assert grid.execution.backend == "fused"
    _assert_results_identical(local, grid, kind)


def test_grid_fused_1x1_batch_matches_local_xla(grid_1x1):
    gs = [graph.generate(64, avg_degree=5.0, kind=k, seed=10 + i)
          for i, k in enumerate(KINDS)]
    p = MatchingProblem.stack(gs)
    local = solve(p, SolveOptions(backend="xla"))
    grid = solve(p, SolveOptions(grid=grid_1x1))
    _assert_results_identical(local, grid, "batch")
    assert int(np.asarray(local.awac_iters).max()) > 0
