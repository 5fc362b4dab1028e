"""Differential + contract suite for the unified solve()/Matcher facade
(repro.core.api, DESIGN.md §7).

Contracts under test:
  - ``solve()`` is bit-identical (mates, duals, AND iteration counts) to
    every legacy entry point it subsumes — ``single.awpm`` on every local
    backend, ``batch.awpm_batched`` on every local backend, and
    ``dist.awpm_dist_batched`` on mesh shapes {1x1, 2x2, 2x4} (the
    multi-device shapes run in an 8-fake-device subprocess, see
    tests/_subproc.py).
  - The legacy entry points are deprecation shims: they emit
    DeprecationWarning and still return bit-identical results.
  - ``SolveOptions`` validates eagerly with clear errors (unknown backend,
    bad grid shape, bad capacities) and a too-small distributed ``cap``
    raises at partition time instead of silently truncating edges.
  - ``plan()``/``Matcher`` reuse one planned engine across calls and reject
    problems that do not match the planned spec.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import run_with_devices
from repro.core import (
    MatchingProblem,
    MatchResult,
    ProblemSpec,
    SolveOptions,
    batch,
    graph,
    plan,
    single,
    solve,
)

LOCAL_BACKENDS = ("reference", "xla")


def _graphs(n=32):
    kinds = [("uniform", 0), ("antigreedy", 7), ("circuit", 2), ("banded", 3)]
    return [graph.generate(n, avg_degree=4.0 + (i % 3), kind=k, seed=s)
            for i, (k, s) in enumerate(kinds)]


def _assert_state_identical(res: MatchResult, state, iters, n, msg=""):
    assert np.array_equal(np.array(res.mate_row), np.array(state.mate_row)), msg
    assert np.array_equal(np.array(res.mate_col), np.array(state.mate_col)), msg
    assert np.array_equal(np.array(res.awac_iters), np.array(iters)), msg


def _legacy(fn, *args, **kwargs):
    """Call a deprecated entry point asserting it warns."""
    with pytest.warns(DeprecationWarning, match="deprecated"):
        return fn(*args, **kwargs)


# --------------------------------------------------------------------------
# local differential: solve() vs single.awpm / batch.awpm_batched
# --------------------------------------------------------------------------


@pytest.mark.parametrize("backend", LOCAL_BACKENDS)
def test_solve_single_bit_identical_to_legacy(backend):
    for g in _graphs():
        res = solve(MatchingProblem.from_graph(g),
                    SolveOptions(backend=backend))
        st, iters = _legacy(
            single.awpm, jnp.asarray(g.row), jnp.asarray(g.col),
            jnp.asarray(g.val), g.n, backend=backend)
        _assert_state_identical(res, st, iters, g.n, backend)
        assert bool(res.perfect)
        assert float(res.weight) == float(single.matching_weight(st, g.n))


@pytest.mark.parametrize("backend", LOCAL_BACKENDS)
def test_solve_batched_bit_identical_to_legacy(backend):
    gs = _graphs()
    problem = MatchingProblem.stack(gs)
    res = solve(problem, SolveOptions(backend=backend))
    st, iters = _legacy(batch.awpm_batched, problem.row, problem.col,
                        problem.val, problem.n, backend=backend)
    _assert_state_identical(res, st, iters, problem.n, backend)
    assert np.array(res.perfect).all()
    # ... and per instance to the single-instance facade route
    for i in range(len(gs)):
        ri = solve(MatchingProblem.from_graph(gs[i]),
                   SolveOptions(backend=backend))
        assert np.array_equal(np.array(res.mate_row[i]),
                              np.array(ri.mate_row))
        assert int(res.awac_iters[i]) == int(ri.awac_iters)


def test_solve_respects_max_iter_and_min_gain():
    g = _graphs()[1]  # antigreedy: needs AWAC rounds
    p = MatchingProblem.from_graph(g)
    r0 = solve(p, SolveOptions(max_iter=0))
    assert int(r0.awac_iters) == 0
    r_full = solve(p)
    assert int(r_full.awac_iters) > 0
    assert float(r_full.weight) > float(r0.weight)
    # a huge min_gain admits no candidate cycles -> AWAC converges in 1 round
    r_gate = solve(p, SolveOptions(min_gain=1e9))
    assert int(r_gate.awac_iters) == 1
    assert float(r_gate.weight) == float(r0.weight)


# --------------------------------------------------------------------------
# 1x1-grid dispatch in-process (single device); multi-device in subprocess
# --------------------------------------------------------------------------


def _mesh_1x1():
    from repro.core.dist import make_mesh

    return make_mesh((1, 1))


def test_solve_grid_1x1_bit_identical_and_dist_shim_warns():
    gs = _graphs()
    problem = MatchingProblem.stack(gs)
    local = solve(problem)
    for backend in ("auto", "fused", "xla"):
        res = solve(problem, SolveOptions(grid=_mesh_1x1(), backend=backend))
        _assert_state_identical(res, local, local.awac_iters, problem.n,
                                f"grid 1x1 {backend}")
    # the deprecated one-shot dist entry point: warns, same bits
    from repro.core import dist

    st, iters, dropped = _legacy(
        dist.awpm_dist_batched, np.asarray(problem.row),
        np.asarray(problem.col), np.asarray(problem.val), problem.n,
        dist.GridSpec(_mesh_1x1()))
    assert int(dropped) == 0
    _assert_state_identical(local, st, iters, problem.n, "dist shim")
    # single-instance problems lift to B=1 on the grid
    p0 = MatchingProblem.from_graph(gs[0])
    r0 = solve(p0, SolveOptions(grid=_mesh_1x1()))
    rl = solve(p0)
    _assert_state_identical(r0, rl, rl.awac_iters, p0.n, "B=1 lift")
    assert np.shape(r0.mate_row) == (p0.n + 1,)


DIST_SCRIPT = r"""
import warnings
import numpy as np, jax
from repro.core import MatchingProblem, SolveOptions, batch, graph, plan, solve
from repro.core.dist import GridSpec, awpm_dist_batched, make_mesh

n = 32
gs = [graph.generate(n, avg_degree=4.0 + (i % 3), kind=k, seed=s)
      for i, (k, s) in enumerate([("uniform", 0), ("antigreedy", 7),
                                  ("circuit", 2), ("banded", 3)])]
problem = MatchingProblem.stack(gs)
oracle = solve(problem)  # local batched facade route (pinned to single.awpm)

for shape in ((1, 1), (2, 2), (2, 4)):
    spec = GridSpec(make_mesh(shape))
    res = solve(problem, SolveOptions(grid=spec))
    assert np.array_equal(np.array(res.mate_row), np.array(oracle.mate_row)), shape
    assert np.array_equal(np.array(res.awac_iters),
                          np.array(oracle.awac_iters)), shape

    # plan once, run twice: same planned engine, same bits
    matcher = plan(problem, SolveOptions(grid=spec))
    r1 = matcher(problem)
    r2 = matcher(problem)
    for a, b in ((r1, oracle), (r2, oracle)):
        assert np.array_equal(np.array(a.mate_row), np.array(b.mate_row)), shape
        assert np.array_equal(np.array(a.awac_iters),
                              np.array(b.awac_iters)), shape

    # legacy one-shot entry point: deprecation warning + identical bits
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        st, iters, dropped = awpm_dist_batched(
            np.asarray(problem.row), np.asarray(problem.col),
            np.asarray(problem.val), n, spec)
    assert any(issubclass(x.category, DeprecationWarning) for x in w), shape
    assert int(dropped) == 0
    assert np.array_equal(np.array(st.mate_row), np.array(oracle.mate_row)), shape
    assert np.array_equal(np.array(iters), np.array(oracle.awac_iters)), shape

# eager options validation that needs a real multi-device mesh: a
# local-sweep backend off the 1x1 grid is rejected at construction
try:
    SolveOptions(grid=GridSpec(make_mesh((2, 2))), backend="xla")
    raise SystemExit("xla backend on a 2x2 grid did not raise")
except ValueError as e:
    assert "1x1 grid" in str(e)

# single-instance lift on a multi-device grid
p0 = MatchingProblem.from_graph(gs[1])
r0 = solve(p0, SolveOptions(grid=GridSpec(make_mesh((2, 2)))))
rl = solve(p0)
assert np.array_equal(np.array(r0.mate_row), np.array(rl.mate_row))
assert int(r0.awac_iters) == int(rl.awac_iters)
print("OK")
"""


def test_solve_and_matcher_across_mesh_shapes():
    """solve()/Matcher vs the local oracle and the legacy dist entry point
    on mesh shapes {1x1, 2x2, 2x4} (8 fake devices)."""
    out = run_with_devices(DIST_SCRIPT, 8)
    assert "OK" in out


# --------------------------------------------------------------------------
# deprecation shims (local, in-process)
# --------------------------------------------------------------------------


def test_legacy_shims_warn_and_match_solve():
    g = _graphs()[0]
    p = MatchingProblem.from_graph(g)
    res = solve(p)
    st, iters = _legacy(single.awpm, jnp.asarray(g.row), jnp.asarray(g.col),
                        jnp.asarray(g.val), g.n)
    _assert_state_identical(res, st, iters, g.n)

    pb = MatchingProblem.stack([g, g])
    resb = solve(pb)
    stb, itersb = _legacy(batch.awpm_batched, pb.row, pb.col, pb.val, pb.n)
    _assert_state_identical(resb, stb, itersb, pb.n)


def test_legacy_dist_factories_warn():
    from repro.core import dist

    spec = dist.GridSpec(_mesh_1x1())
    # record=True exposes the attributed filename: the warning must point
    # at THIS call site (the migration target), not the dataclass-generated
    # __init__ or the shim internals
    with pytest.warns(DeprecationWarning, match="DistBatchedAWPM") as rec:
        dist.DistBatchedAWPM(spec, 8)
    assert rec[0].filename == __file__
    with pytest.warns(DeprecationWarning, match="DistAWPM") as rec:
        dist.DistAWPM(spec, 8, cap=16, a2a_caps=(16, 16))
    assert rec[0].filename == __file__
    with pytest.warns(DeprecationWarning, match="make_awpm_dist_batched") as rec:
        dist.make_awpm_dist_batched(spec, 8, 1, 16, (16, 16))
    assert rec[0].filename == __file__


# --------------------------------------------------------------------------
# SolveOptions / MatchingProblem validation error paths
# --------------------------------------------------------------------------


@pytest.mark.parametrize("kwargs,match", [
    (dict(backend="bogus"), "unknown backend"),
    # the Pallas sweeps were deleted: their names are no backends any more
    (dict(backend="pallas"), "unknown backend"),
    (dict(backend="pallas_persistent"), "unknown backend"),
    (dict(backend="fused"), "requires SolveOptions.grid"),
    (dict(max_iter=-1), "max_iter"),
    (dict(max_iter=1.5), "max_iter"),
    (dict(min_gain=float("nan")), "min_gain"),
    (dict(min_gain=-1.0), "min_gain"),
    (dict(window_steps=0), "window_steps"),
    (dict(window_steps=True), "window_steps"),
    (dict(cap=0), "cap must be"),
    (dict(cap=64), "requires SolveOptions.grid"),
    (dict(a2a_caps=(8, 8)), "requires SolveOptions.grid"),
    (dict(a2a_caps=(8,)), "a2a_caps"),
    (dict(packed=True), "requires SolveOptions.grid"),
    (dict(grid="nope"), "grid must be"),
], ids=lambda x: str(x)[:40])
def test_options_validation_errors(kwargs, match):
    with pytest.raises(ValueError, match=match):
        SolveOptions(**kwargs)


def test_options_accept_numpy_integers():
    o = SolveOptions(max_iter=np.int32(100), window_steps=np.int64(8))
    assert o.max_iter == 100 and type(o.max_iter) is int
    assert o.window_steps == 8 and type(o.window_steps) is int
    od = SolveOptions(grid=_mesh_1x1(), cap=np.int64(128),
                      a2a_caps=(np.int32(8), np.int32(16)))
    assert od.cap == 128 and od.a2a_caps == (8, 16)


def test_options_bad_grid_shape():
    mesh = jax.make_mesh((1, 1), ("x", "y"))
    with pytest.raises(ValueError, match="bad grid shape"):
        SolveOptions(grid=mesh)
    # (xla off the 1x1 grid is also rejected eagerly — covered in the
    # multi-device subprocess script, which can build a 2x2 mesh)


# --------------------------------------------------------------------------
# MatchResult.execution: the engine that ran and how it was chosen
# --------------------------------------------------------------------------


def test_solve_records_explicit_execution():
    p = MatchingProblem.from_graph(_graphs()[0])
    for backend in LOCAL_BACKENDS:
        res = solve(p, SolveOptions(backend=backend))
        assert res.execution.backend == backend
        assert res.execution.source == "explicit"
        assert res.execution.warm_started is False
    res = solve(p, SolveOptions(backend="fused", grid=_mesh_1x1()))
    assert (res.execution.backend, res.execution.source) == \
        ("fused", "explicit")


def test_solve_records_default_source():
    # "auto" is the fused XLA sweep locally, single and batched, and the
    # fused engine on a grid
    gs = _graphs()
    for problem in (MatchingProblem.from_graph(gs[0]),
                    MatchingProblem.stack(gs)):
        res = solve(problem)
        assert (res.execution.backend, res.execution.source) == \
            ("xla", "default")
    res = solve(MatchingProblem.from_graph(gs[0]),
                SolveOptions(grid=_mesh_1x1()))
    assert (res.execution.backend, res.execution.source) == \
        ("fused", "default")


def test_dist_cap_too_small_refuses_to_truncate():
    problem = MatchingProblem.stack(_graphs())
    with pytest.raises(ValueError, match="refusing to truncate"):
        solve(problem, SolveOptions(grid=_mesh_1x1(), cap=4))


def test_dist_user_a2a_caps_that_drop_raise():
    """Undersized user-supplied exchange buckets would silently break the
    bit-identity contract (requests dropped mid-exchange), so the facade
    raises instead of returning a degraded matching."""
    problem = MatchingProblem.stack(_graphs())
    with pytest.raises(RuntimeError, match="dropped"):
        solve(problem, SolveOptions(grid=_mesh_1x1(), a2a_caps=(1, 1)))


def test_undersized_window_steps_clamps_up_not_breaks():
    """An explicit window_steps below the measured need is clamped UP
    (extra depth never changes results; under-depth would silently miss
    completion edges) — results stay bit-identical on every route."""
    gs = _graphs()
    problem = MatchingProblem.stack(gs)
    oracle = solve(problem)
    for opts in (SolveOptions(window_steps=1, backend="xla"),
                 SolveOptions(window_steps=1, grid=_mesh_1x1())):
        res = solve(problem, opts)
        _assert_state_identical(res, oracle, oracle.awac_iters, problem.n,
                                str(opts))
    p0 = MatchingProblem.from_graph(gs[1])
    r0 = solve(p0, SolveOptions(window_steps=1, backend="xla"))
    rl = solve(p0)
    _assert_state_identical(r0, rl, rl.awac_iters, p0.n)
    # ... and under jit, where the need cannot be measured: the provable
    # window_depth(min(cap, n)) bound stands in, same bits as eager
    rj = jax.jit(
        lambda pr: solve(pr, SolveOptions(window_steps=1, backend="xla"))
    )(p0)
    _assert_state_identical(rj, rl, rl.awac_iters, p0.n, "jit clamp")


def test_matcher_dist_plan_time_engine_build_is_reused():
    from repro.core.dist import _make_awpm_dist_batched

    problem = MatchingProblem.stack(_graphs())
    matcher = plan(problem, SolveOptions(grid=_mesh_1x1()))
    info = _make_awpm_dist_batched.cache_info()
    matcher(problem)
    after = _make_awpm_dist_batched.cache_info()
    assert after.misses == info.misses, "first call rebuilt the engine"
    assert after.hits == info.hits + 1
    # an undersized window_steps pin is lifted to the block bound at plan
    # time, so the cache-hit property survives the override too
    m2 = plan(problem, SolveOptions(grid=_mesh_1x1(), window_steps=1))
    info2 = _make_awpm_dist_batched.cache_info()
    m2(problem)
    after2 = _make_awpm_dist_batched.cache_info()
    assert after2.misses == info2.misses, "undersized pin rebuilt the engine"


def test_problem_and_result_identity_semantics():
    """Array-field pytrees use identity == / hash (eq=False): comparing or
    hashing must never raise the numpy truth-value/unhashable errors."""
    g = _graphs()[0]
    p = MatchingProblem.from_graph(g)
    q = MatchingProblem.from_graph(g)
    assert p == p and p != q  # identity, no ambiguous-truth-value raise
    assert {p: 1}[p] == 1  # hashable
    r = solve(p, SolveOptions(max_iter=0))
    assert r == r and hash(r) == hash(r)


def test_matcher_dist_denser_than_prototype_gives_replan_error():
    """A prototype-planned block capacity has zero headroom; a same-spec
    but denser problem must fail with re-plan guidance, not the
    partition-internal plan_block_cap advice."""
    n, cap = 16, 64
    ii = np.arange(n, dtype=np.int32)
    sparse = MatchingProblem.from_coo(ii, ii, np.full(n, 0.5, np.float32),
                                      n, capacity=cap)
    g = graph.generate(n, avg_degree=3.0, kind="uniform", seed=0)
    m = np.arange(g.capacity) < g.nnz
    dense = MatchingProblem.from_coo(g.row[m], g.col[m], g.val[m], n,
                                     capacity=cap)
    matcher = plan(sparse, SolveOptions(grid=_mesh_1x1()))
    assert np.array_equal(np.array(matcher(sparse).mate_row[:n]), ii)
    with pytest.raises(ValueError, match="plan\\(\\) again"):
        matcher(dense)


def test_matcher_dist_rejects_cap_mismatch():
    problem = MatchingProblem.stack(_graphs())
    matcher = plan(problem, SolveOptions(grid=_mesh_1x1()))
    wrong_cap = MatchingProblem(
        row=np.asarray(problem.row)[:, :-8],
        col=np.asarray(problem.col)[:, :-8],
        val=np.asarray(problem.val)[:, :-8], n=problem.n)
    with pytest.raises(ValueError, match="planned cap"):
        matcher(wrong_cap)


def test_problem_validation_and_constructors():
    g = _graphs()[0]
    with pytest.raises(ValueError, match="shapes differ"):
        MatchingProblem(row=g.row, col=g.col[:-1], val=g.val, n=g.n)
    with pytest.raises(ValueError, match="expected"):
        MatchingProblem(row=g.row.reshape(1, 1, -1),
                        col=g.col.reshape(1, 1, -1),
                        val=g.val.reshape(1, 1, -1), n=g.n)
    with pytest.raises(ValueError, match="at least one"):
        MatchingProblem.stack([])
    with pytest.raises(TypeError, match="BipartiteGraphs or MatchingProblems"):
        MatchingProblem.stack([object()])
    with pytest.raises(TypeError, match="MatchingProblem"):
        solve("not a problem")
    with pytest.raises(TypeError, match="SolveOptions"):
        solve(MatchingProblem.from_graph(g), options="fast please")

    # from_coo sorts + pads; stack accepts problems and graphs alike
    rng = np.random.default_rng(0)
    order = rng.permutation(g.nnz)
    m = np.arange(g.capacity) < g.nnz
    p1 = MatchingProblem.from_coo(g.row[m][order], g.col[m][order],
                                  g.val[m][order], g.n)
    p2 = MatchingProblem.from_graph(g)
    assert np.array_equal(np.asarray(p1.row), np.asarray(p2.row))
    st = MatchingProblem.stack([p1, g])
    assert st.batch_size == 2 and st.n == g.n
    assert np.array_equal(np.asarray(st.row[0]), np.asarray(st.row[1]))
    assert p1.batch_size is None and not p1.is_batched and st.is_batched
    assert p1.spec == ProblemSpec(n=g.n, cap=p1.cap, batch=None)
    # numpy integers (off array shapes) normalize instead of failing
    assert ProblemSpec(n=np.int32(8), cap=np.int64(16),
                       batch=np.int32(2)) == ProblemSpec(8, 16, 2)
    pnp = MatchingProblem(row=g.row, col=g.col, val=g.val, n=np.int32(g.n))
    assert plan(pnp).problem_spec.n == g.n


def test_problem_is_a_pytree():
    g = _graphs()[0]
    p = MatchingProblem.from_graph(g)

    @jax.jit
    def weight_inside_jit(problem):
        res = solve(problem, SolveOptions(backend="reference"))
        return res.weight, res.awac_iters

    w, iters = weight_inside_jit(p)
    res = solve(p, SolveOptions(backend="reference"))
    assert float(w) == float(res.weight)
    assert int(iters) == int(res.awac_iters)
    leaves, treedef = jax.tree_util.tree_flatten(p)
    assert len(leaves) == 3
    p2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert p2.n == p.n and np.array_equal(np.asarray(p2.row),
                                          np.asarray(p.row))


def test_solve_grid_under_jit_raises_clearly():
    """The distributed route partitions on the host; under jit it must fail
    with the facade's own message, not an opaque tracer-conversion error."""
    problem = MatchingProblem.stack(_graphs()[:2])
    opts = SolveOptions(grid=_mesh_1x1())
    with pytest.raises(TypeError, match="outside\\s+jit"):
        jax.jit(lambda p: solve(p, opts))(problem)
    # a partially-traced problem (only val is a tracer) must hit the same
    # clear error, not an opaque tracer-conversion failure
    r, c = np.asarray(problem.row), np.asarray(problem.col)
    with pytest.raises(TypeError, match="outside\\s+jit"):
        jax.jit(lambda v: solve(
            MatchingProblem(row=r, col=c, val=v, n=problem.n), opts)
        )(problem.val)


def test_solve_under_jit_default_options_bit_identical():
    """jit(solve) with DEFAULT options (auto -> xla on CPU) must work and
    stay bit-identical to the eager call: the packed-key x64 scopes are
    skipped inside an outer trace (single._x64_scope) and the two-pass
    fallback reductions take over."""
    gs = _graphs()
    jit_solve = jax.jit(lambda pr: solve(pr))
    p = MatchingProblem.from_graph(gs[1])
    eager = solve(p)
    jitted = jit_solve(p)
    _assert_state_identical(jitted, eager, eager.awac_iters, p.n, "single")
    pb = MatchingProblem.stack(gs)
    eb = solve(pb)
    jb = jax.jit(lambda pr: solve(pr))(pb)
    _assert_state_identical(jb, eb, eb.awac_iters, pb.n, "batched")


# --------------------------------------------------------------------------
# Matcher (local): spec pinning + reuse
# --------------------------------------------------------------------------


def test_matcher_local_reuse_and_spec_checks():
    gs = _graphs()
    problem = MatchingProblem.stack(gs)
    matcher = plan(problem, SolveOptions(backend="xla"))
    r1 = matcher(problem)
    r2 = matcher(MatchingProblem.stack(list(reversed(gs))))
    oracle = solve(problem, SolveOptions(backend="xla"))
    _assert_state_identical(r1, oracle, oracle.awac_iters, problem.n)
    assert np.array_equal(np.array(r2.mate_row[::-1]),
                          np.array(r1.mate_row))

    single_p = MatchingProblem.from_graph(gs[0])
    with pytest.raises(ValueError, match="does not match the planned spec"):
        matcher(single_p)
    wrong_cap = MatchingProblem(
        row=np.asarray(problem.row)[:, :-8], col=np.asarray(problem.col)[:, :-8],
        val=np.asarray(problem.val)[:, :-8], n=problem.n)
    with pytest.raises(ValueError, match="planned cap"):
        matcher(wrong_cap)
    with pytest.raises(TypeError, match="ProblemSpec or a prototype"):
        plan("spec?")

    # plan from a bare ProblemSpec (no prototype data)
    m2 = plan(ProblemSpec(n=problem.n, cap=problem.cap,
                          batch=problem.batch_size))
    r3 = m2(problem)
    ref = solve(problem)
    _assert_state_identical(r3, ref, ref.awac_iters, problem.n)
