"""Guarded execution (runtime.resilient, DESIGN.md §9): deadlines, bounded
retry, the backend degradation chain, and the post-solve verifier. Fault
injection comes from runtime.chaos; everything here runs in-process on a
1x1 grid at most."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import MatchingProblem, SolveOptions, graph, solve
from repro.core.dual import DualCertificate
from repro.runtime import chaos, elastic
from repro.runtime.resilient import (
    DeadlineExceededError,
    ResilientMatcher,
    ResilientOptions,
    TransientFault,
    VerificationError,
    _build_rungs,
    resilient_solve,
    verify_result,
)


def _problem(n=16, seed=0):
    return MatchingProblem.from_graph(
        graph.generate(n, avg_degree=4.0, seed=seed))


def _mesh11():
    return jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


# --------------------------------------------------------------------------
# the happy path
# --------------------------------------------------------------------------


def test_serves_first_rung_with_clean_report():
    rr = resilient_solve(_problem())
    assert bool(rr.result.perfect)
    assert not rr.report.degraded
    assert rr.report.backend_used.startswith("local ")
    (attempt,) = rr.report.attempts
    assert attempt.outcome == "ok" and attempt.retry == 0


def test_certify_attaches_dual_certificate():
    rr = resilient_solve(
        _problem(), resilience=ResilientOptions(certify=True,
                                                verify_convergence=True))
    assert isinstance(rr.report.certificate, DualCertificate)
    assert rr.report.certificate.upper_bound >= float(rr.result.weight) - 1e-6


def test_options_validation():
    with pytest.raises(ValueError, match="deadline_s"):
        ResilientOptions(deadline_s=0.0)
    with pytest.raises(ValueError, match="max_retries"):
        ResilientOptions(max_retries=-1)


# --------------------------------------------------------------------------
# the verifier
# --------------------------------------------------------------------------


def test_verify_result_passes_on_honest_results():
    p = _problem()
    assert verify_result(p, solve(p)) == ()


def test_verify_result_catches_corruption():
    p = _problem()
    res = solve(p)
    mr = np.asarray(res.mate_row).copy()
    mr[0] = mr[1]  # two columns now claim one row
    bad = dataclasses.replace(res, mate_row=mr)
    fails = verify_result(p, bad)
    assert any("two columns to one row" in f for f in fails)
    # a forged weight is caught by the recompute
    forged = dataclasses.replace(res, weight=np.asarray(res.weight) + 1.0)
    assert any("recomputed weight" in f for f in verify_result(p, forged))
    # a forged perfect flag is caught by the matched-column count
    flagged = dataclasses.replace(res, perfect=np.asarray(False))
    assert any("perfect flag" in f for f in verify_result(p, flagged))


def test_verify_result_batched_labels_instances():
    p = MatchingProblem.stack([_problem(seed=0), _problem(seed=1)])
    res = solve(p)
    mc = np.asarray(res.mate_col).copy()
    mc[1, p.n] = 0  # corrupt instance 1's sentinel slot
    fails = verify_result(p, dataclasses.replace(res, mate_col=mc))
    assert fails and all(f.startswith("[instance 1]") for f in fails)


# --------------------------------------------------------------------------
# retry + degradation
# --------------------------------------------------------------------------


def test_transient_failure_retries_on_same_rung():
    p = _problem()
    with chaos.failing_backend("xla", fail_times=1):
        rr = resilient_solve(p)
    assert [a.outcome for a in rr.report.attempts] == ["transient", "ok"]
    assert not rr.report.degraded
    assert bool(rr.result.perfect)


def test_persistent_failure_degrades_to_reference():
    p = _problem()
    ref = solve(p, SolveOptions(backend="reference"))
    with chaos.failing_backend("xla"):
        rr = resilient_solve(p)
    assert rr.report.backend_used == "local reference"
    assert rr.report.degraded
    assert np.array_equal(np.asarray(rr.result.mate_row),
                          np.asarray(ref.mate_row))


def test_deadline_expires_with_report():
    p = _problem()
    with chaos.failing_backend("xla", "reference",
                               exc_type=TransientFault):
        with pytest.raises(DeadlineExceededError) as exc:
            resilient_solve(p, resilience=ResilientOptions(
                deadline_s=0.2, max_retries=1000, backoff_s=0.05))
    assert all(a.outcome == "transient" for a in exc.value.report.attempts)


def test_every_rung_failing_raises_verification_error():
    p = _problem()
    with chaos.failing_backend("xla", "reference",
                               exc_type=RuntimeError):
        with pytest.raises(VerificationError) as exc:
            resilient_solve(p, SolveOptions(backend="xla"),
                            resilience=ResilientOptions(
                                max_retries=0, backoff_s=0.0))
    # one transient attempt per local rung (xla, ref), no retries
    assert len(exc.value.report.attempts) == 2


def test_request_errors_propagate_untouched():
    g = graph.generate(10, avg_degree=3.0, seed=1)
    keep = np.asarray(g.col) != 4
    infeasible = MatchingProblem.from_coo(np.asarray(g.row)[keep],
                                          np.asarray(g.col)[keep],
                                          np.asarray(g.val)[keep], g.n)
    from repro.core import InfeasibleProblemError

    with pytest.raises(InfeasibleProblemError):
        resilient_solve(infeasible)


# --------------------------------------------------------------------------
# the degradation chain itself
# --------------------------------------------------------------------------


def test_rung_labels_without_grid():
    labels = [lbl for lbl, _ in _build_rungs(SolveOptions())]
    assert labels == ["local xla", "local reference"]
    labels = [lbl for lbl, _ in _build_rungs(SolveOptions(backend="xla"))]
    assert labels == ["local xla", "local reference"]
    labels = [lbl for lbl, _ in
              _build_rungs(SolveOptions(backend="reference"))]
    assert labels == ["local reference"]


def test_grid_rung_strips_distributed_knobs_on_fallback():
    rungs = _build_rungs(SolveOptions(grid=_mesh11(), exchange_check=True,
                                      packed=True))
    assert rungs[0][0] == "grid 1x1 (fused)"
    for label, opts in rungs[1:]:
        assert label.startswith("local ")
        assert opts.grid is None and not opts.exchange_check \
            and not opts.packed


def test_dead_fleet_skips_the_grid_rung():
    mesh = _mesh11()
    fleet = elastic.fail_hosts(elastic.initial_fleet(mesh),
                               [np.asarray(mesh.devices)[0, 0].id])
    labels = [lbl for lbl, _ in
              _build_rungs(SolveOptions(grid=mesh), fleet=fleet)]
    assert all(lbl.startswith("local ") for lbl in labels)


def test_grid_request_degrades_to_local_when_engine_dies():
    p = _problem()
    ref = solve(p)
    with chaos.failing_grid():
        rr = resilient_solve(p, SolveOptions(grid=_mesh11()))
    assert rr.report.degraded
    assert rr.report.backend_used.startswith("local ")
    assert np.array_equal(np.asarray(rr.result.mate_row),
                          np.asarray(ref.mate_row))


# --------------------------------------------------------------------------
# ResilientMatcher
# --------------------------------------------------------------------------


def test_resilient_matcher_serves_and_caches():
    p = _problem()
    m = ResilientMatcher(p)
    r1 = m(p)
    r2 = m(p)
    assert bool(r1.result.perfect)
    assert np.array_equal(np.asarray(r1.result.mate_row),
                          np.asarray(r2.result.mate_row))
    assert len(m._matchers) == 1  # one planned Matcher, reused


def test_resilient_matcher_degrades_like_solve():
    p = _problem()
    with chaos.failing_backend("xla"):
        rr = ResilientMatcher(p)(p)
    assert rr.report.backend_used == "local reference"
