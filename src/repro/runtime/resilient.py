"""Guarded AWPM execution: deadlines, bounded retry, backend degradation,
and post-solve verification over ``repro.core.api``.

The serving tier the ROADMAP targets cannot call ``solve()`` naked: a
Pallas kernel can miscompile on a new toolchain, a device can drop out
mid-exchange, a transient XLA runtime error can kill an otherwise healthy
request, and a silently wrong matching poisons the downstream
factorization it exists to stabilize. ``resilient_solve`` wraps the facade
with the standard serving guards:

  - **wall-clock deadline** — the request fails fast with
    ``DeadlineExceededError`` instead of hanging a caller;
  - **bounded retry with exponential backoff** for transient failures
    (``TransientFault``, XLA runtime errors) on the same rung;
  - **backend degradation chain** — the requested engine first, then each
    strictly-more-conservative rung: a grid engine falls back to the local
    engines, ``pallas -> xla -> reference``, leaving out rungs the
    platform cannot compile (no Pallas rung on TPU); the rung that finally
    served the request is recorded, never hidden;
  - **device-loss recovery** — with a ``runtime.elastic.FleetState``, a
    dead device folds the grid down to ``surviving_mesh`` before the grid
    rung runs (and to the local chain when no full row survived);
  - **post-solve verification** — structural invariants (mate bijectivity,
    matched edges exist in the instance, recomputed weight, perfect-flag
    consistency) and optionally a convergence audit (one reference
    winner-search pass: a converged result must admit no augmenting
    4-cycle) and a ``core.dual`` optimality certificate.

Every attempt, fallback, verification outcome, and the serving rung land
on the returned ``ResilienceReport`` — surfaced, never swallowed. Errors
that reflect the *request* rather than the *execution* (bad types/options,
``PreflightError``, ``InfeasibleProblemError``) propagate immediately:
no amount of retrying fixes an infeasible instance.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import numpy as np

from repro.core import api as _api
from repro.core import single as _single
from repro.core.dist import ExchangeIntegrityError
from repro.core.preflight import PreflightError

__all__ = [
    "DeadlineExceededError",
    "ResilienceReport",
    "ResilientMatcher",
    "ResilientOptions",
    "ResilientResult",
    "TransientFault",
    "VerificationError",
    "resilient_solve",
    "verify_result",
]


class TransientFault(RuntimeError):
    """A failure worth retrying on the same rung (injected by the chaos
    harness; real analogues: preempted device, flaky interconnect)."""


class DeadlineExceededError(RuntimeError):
    """The wall-clock deadline expired before any rung produced a verified
    result. Carries the partial ``report``."""

    def __init__(self, message: str, report: "ResilienceReport"):
        self.report = report
        super().__init__(message)


class VerificationError(RuntimeError):
    """Every rung either failed or produced a result that flunked
    post-solve verification. Carries the full ``report`` — the verifier
    failures per rung are in its attempts."""

    def __init__(self, message: str, report: "ResilienceReport"):
        self.report = report
        super().__init__(message)


@dataclasses.dataclass(frozen=True)
class ResilientOptions:
    """Guard knobs, orthogonal to ``SolveOptions`` (which keeps owning the
    algorithm).

    deadline_s        wall-clock budget across ALL rungs/retries (None =
                      unbounded).
    max_retries       same-rung retries for transient failures.
    backoff_s         first retry delay; grows by ``backoff_factor``.
    verify            run the structural post-solve verifier on every
                      candidate result (a failure moves to the next rung).
    verify_convergence  additionally audit convergence with one reference
                      winner-search pass (catches a prematurely-converged
                      loop — e.g. a flipped convergence mask).
    certify           attach a ``core.dual`` certificate to perfect
                      results (skipped silently for imperfect ones).
    """

    deadline_s: float | None = None
    max_retries: int = 2
    backoff_s: float = 0.05
    backoff_factor: float = 2.0
    verify: bool = True
    verify_convergence: bool = False
    certify: bool = False

    def __post_init__(self):
        if self.deadline_s is not None and not self.deadline_s > 0:
            raise ValueError(
                f"deadline_s must be positive or None, got {self.deadline_s!r}")
        if not isinstance(self.max_retries, int) or self.max_retries < 0:
            raise ValueError(
                f"max_retries must be a non-negative int, got "
                f"{self.max_retries!r}")


@dataclasses.dataclass(frozen=True)
class Attempt:
    """One execution attempt: which rung, what happened."""

    rung: str  # e.g. "grid 2x4 (fused)", "local xla"
    outcome: str  # "ok" | "transient" | "integrity" | "unsupported"
    #               | "verify_failed"
    detail: str = ""
    wall_s: float = 0.0
    retry: int = 0  # 0 = first try on this rung


@dataclasses.dataclass(frozen=True)
class ResilienceReport:
    """Everything that happened while serving one request."""

    attempts: tuple[Attempt, ...]
    backend_used: str | None = None  # rung label that served the request
    degraded: bool = False  # served by a rung below the requested one
    verification: tuple[str, ...] = ()  # failures of the SERVED result ( () = clean)
    certificate: Any = None  # core.dual certificate(s) when requested

    def summary(self) -> str:
        served = self.backend_used or "unserved"
        flag = " (degraded)" if self.degraded else ""
        return (f"served by {served}{flag} after {len(self.attempts)} "
                f"attempt(s)")


@dataclasses.dataclass(frozen=True)
class ResilientResult:
    """A ``MatchResult`` plus the serving story."""

    result: _api.MatchResult
    report: ResilienceReport


# --------------------------------------------------------------------------
# post-solve verification
# --------------------------------------------------------------------------


def _verify_instance(row, col, val, n, mate_row, mate_col, weight, perfect,
                     iters, max_iter, min_gain, check_convergence, label):
    """Invariant checks for one instance (host numpy). Returns failures."""
    fails = []
    mr = np.asarray(mate_row)
    mc = np.asarray(mate_col)
    if mr.shape != (n + 1,) or mc.shape != (n + 1,):
        return [f"{label}mate arrays have wrong shape {mr.shape}/{mc.shape}"]
    if mr[n] != n or mc[n] != n:
        fails.append(f"{label}sentinel slot corrupted: mate_row[n]={mr[n]}, "
                     f"mate_col[n]={mc[n]}")
    if ((mr < 0) | (mr > n)).any() or ((mc < 0) | (mc > n)).any():
        fails.append(f"{label}mate entries outside [0, n]")
        return fails
    # partial bijection: matched columns map to distinct rows and the two
    # mate arrays are mutual inverses on the matched set
    cols = np.flatnonzero(mr[:n] < n)
    rows = mr[cols]
    if np.unique(rows).size != rows.size:
        fails.append(f"{label}mate_row maps two columns to one row")
    elif not (mc[rows] == cols).all():
        fails.append(f"{label}mate_row/mate_col are not mutual inverses")
    rows2 = np.flatnonzero(mc[:n] < n)
    if rows2.size != cols.size:
        fails.append(f"{label}matched-row count {rows2.size} != "
                     f"matched-column count {cols.size}")
    # matched edges must exist in the instance; recompute the weight
    real = np.asarray(row) < n
    key = np.asarray(row)[real].astype(np.int64) * (n + 1) \
        + np.asarray(col)[real]
    order = np.argsort(key, kind="stable")
    skey = key[order]
    sval = np.asarray(val)[real][order]
    qkey = rows.astype(np.int64) * (n + 1) + cols
    pos = np.searchsorted(skey, qkey)
    found = (pos < skey.size) & (skey[np.clip(pos, 0, skey.size - 1)] == qkey)
    if not found.all():
        miss = np.flatnonzero(~found)[0]
        fails.append(f"{label}matched edge ({int(rows[miss])}, "
                     f"{int(cols[miss])}) is not in the edge list")
    else:
        w = float(sval[pos].sum()) if qkey.size else 0.0
        if not np.isclose(w, float(weight), rtol=1e-4, atol=1e-4):
            fails.append(f"{label}recomputed weight {w:.6g} != reported "
                         f"{float(weight):.6g}")
    if bool(perfect) != (cols.size == n):
        fails.append(f"{label}perfect flag {bool(perfect)} inconsistent "
                     f"with {cols.size}/{n} matched columns")
    if check_convergence and bool(perfect) and int(iters) < int(max_iter) \
            and not fails:
        # a converged result must admit no augmenting 4-cycle: one
        # reference winner-search pass over the final state
        import jax.numpy as jnp

        state = _single.state_from_mates(
            jnp.asarray(row), jnp.asarray(col), jnp.asarray(val), n,
            jnp.asarray(mr), jnp.asarray(mc))
        Cgain, _, _, _ = _single.awac_cwinners(
            jnp.asarray(row), jnp.asarray(col), jnp.asarray(val), n, state,
            min_gain)
        if bool((np.asarray(Cgain) > min_gain).any()):
            fails.append(
                f"{label}result reported converged after {int(iters)} "
                f"round(s) but still admits an augmenting 4-cycle "
                f"(convergence mask was wrong)")
    return fails


def verify_result(problem: _api.MatchingProblem, result: _api.MatchResult,
                  options: _api.SolveOptions | None = None,
                  check_convergence: bool = False) -> tuple[str, ...]:
    """Re-check the permutation invariant and the reported weight of
    ``result`` against ``problem`` from scratch (host-side, independent of
    every engine). Returns a tuple of human-readable failures — empty means
    verified."""
    options = options or _api.SolveOptions()
    n = int(problem.n)
    if problem.is_batched:
        fails = []
        for bi in range(problem.batch_size):
            fails += _verify_instance(
                np.asarray(problem.row)[bi], np.asarray(problem.col)[bi],
                np.asarray(problem.val)[bi], n,
                np.asarray(result.mate_row)[bi],
                np.asarray(result.mate_col)[bi],
                np.asarray(result.weight)[bi],
                np.asarray(result.perfect)[bi],
                np.asarray(result.awac_iters)[bi], options.max_iter,
                options.min_gain, check_convergence, f"[instance {bi}] ")
        return tuple(fails)
    return tuple(_verify_instance(
        problem.row, problem.col, problem.val, n, result.mate_row,
        result.mate_col, result.weight, result.perfect, result.awac_iters,
        options.max_iter, options.min_gain, check_convergence, ""))


# --------------------------------------------------------------------------
# degradation chain
# --------------------------------------------------------------------------


#: most-aggressive to most-conservative: the persistent whole-loop kernel
#: degrades to the per-sweep kernel, then the fused XLA sweep, then the seed
#: reference path. Rungs the platform cannot compile are left out
#: (``core.single.backend_available``).
_LOCAL_CHAIN = ("pallas_persistent", "pallas", "xla", "reference")


def _local_options(options: _api.SolveOptions,
                   backend: str) -> _api.SolveOptions:
    """Strip the distributed-only knobs so a grid request can degrade to a
    local rung."""
    return dataclasses.replace(
        options, grid=None, cap=None, a2a_caps=None, packed=False,
        exchange_check=False, backend=backend)


def _build_rungs(options: _api.SolveOptions, fleet=None):
    """The degradation chain as (label, SolveOptions) pairs: the requested
    engine first, then every strictly-more-conservative rung."""
    rungs = []
    if options.grid is not None:
        grid = options.grid
        if fleet is not None and not fleet.alive.all():
            from repro.runtime import elastic

            try:
                mesh = elastic.surviving_mesh(fleet)
                grid = dataclasses.replace(
                    options, grid=mesh).grid  # re-validate via SolveOptions
                rungs.append((
                    f"grid {grid.pr}x{grid.pc} ({options._dist_backend()}, "
                    f"shrunk)", dataclasses.replace(options, grid=mesh)))
            except RuntimeError:
                pass  # no usable grid survived: straight to the local chain
        else:
            rungs.append((
                f"grid {grid.pr}x{grid.pc} ({options._dist_backend()})",
                options))
    start = _single.resolve_backend(options.backend) \
        if options.backend == "auto" else options.backend
    if start not in _LOCAL_CHAIN:  # "fused" is grid-only: full local chain
        start = _LOCAL_CHAIN[0]
    for b in _LOCAL_CHAIN[_LOCAL_CHAIN.index(start):]:
        if _single.backend_available(b):
            rungs.append((f"local {b}", _local_options(options, b)))
    return rungs


def _is_compiler_refusal(exc: BaseException) -> bool:
    """A lowering or compiler that refuses the program: the same rung will
    refuse it again, however often it is retried. Mosaic raises both of
    its known refusals of the sweep kernels as ``NotImplementedError``."""
    return isinstance(exc, NotImplementedError)


def _classify(exc: BaseException) -> str:
    """fatal: the request is wrong — propagate. integrity: this rung's
    result can't be trusted — next rung, no retry. unsupported: the
    compiler refused this rung — next rung, no retry, recorded.
    transient: same rung is worth retrying."""
    if isinstance(exc, PreflightError):
        return "fatal"
    if isinstance(exc, (TypeError, ValueError)):
        return "fatal"
    if isinstance(exc, ExchangeIntegrityError):
        return "integrity"
    if _is_compiler_refusal(exc):
        return "unsupported"
    return "transient"  # TransientFault, XlaRuntimeError, other RuntimeErrors


# --------------------------------------------------------------------------
# the guarded loop
# --------------------------------------------------------------------------


def _serve(problem, rungs, requested_label, options, resilience, run_rung):
    start_t = time.monotonic()
    attempts: list[Attempt] = []

    def remaining():
        if resilience.deadline_s is None:
            return None
        return resilience.deadline_s - (time.monotonic() - start_t)

    def fail(exc_cls, msg):
        report = ResilienceReport(attempts=tuple(attempts))
        raise exc_cls(msg + f" [{report.summary()}]", report)

    for label, opts in rungs:
        retry = 0
        while True:
            left = remaining()
            if left is not None and left <= 0:
                fail(DeadlineExceededError,
                     f"deadline {resilience.deadline_s}s expired before any "
                     f"rung produced a verified result")
            t0 = time.monotonic()
            try:
                result = run_rung(label, opts)
            except Exception as e:
                kind = _classify(e)
                if kind == "fatal":
                    raise
                attempts.append(Attempt(
                    rung=label, outcome=kind,
                    detail=f"{type(e).__name__}: {e}",
                    wall_s=time.monotonic() - t0, retry=retry))
                if kind != "transient" or retry >= resilience.max_retries:
                    break  # next rung
                delay = resilience.backoff_s * \
                    resilience.backoff_factor ** retry
                if (left := remaining()) is not None:
                    delay = min(delay, max(left, 0.0))
                time.sleep(delay)
                retry += 1
                continue
            wall = time.monotonic() - t0
            fails = ()
            if resilience.verify:
                fails = verify_result(
                    problem, result, opts,
                    check_convergence=resilience.verify_convergence)
            if fails:
                attempts.append(Attempt(
                    rung=label, outcome="verify_failed",
                    detail="; ".join(fails), wall_s=wall, retry=retry))
                break  # a wrong result is not retryable on the same rung
            attempts.append(Attempt(rung=label, outcome="ok", wall_s=wall,
                                    retry=retry))
            cert = None
            if resilience.certify and bool(
                    np.asarray(result.perfect).all()):
                from repro.core import dual as _dual

                cert = _dual.certify(problem, result)
            report = ResilienceReport(
                attempts=tuple(attempts), backend_used=label,
                degraded=label != requested_label, verification=fails,
                certificate=cert)
            return ResilientResult(result=result, report=report)
    fail(VerificationError,
         "every rung failed or produced a result that flunked verification")


def resilient_solve(problem: _api.MatchingProblem,
                    options: _api.SolveOptions | None = None,
                    resilience: ResilientOptions | None = None,
                    fleet=None, warm_start=None) -> ResilientResult:
    """``core.api.solve`` behind the full guard stack (module docstring).
    ``fleet`` is an optional ``runtime.elastic.FleetState`` consulted
    before the grid rung. ``warm_start`` threads straight through to
    ``solve`` on every rung (warm-start rematching, DESIGN.md §11) — a
    seed the facade rejects as stale raises immediately (fatal: the
    *request* is wrong, no rung can fix it; the serving tier's
    ``serving.warm.solve_with_seed`` owns the cold fallback). Returns a
    :class:`ResilientResult`; raises ``DeadlineExceededError`` /
    ``VerificationError`` (each carrying the report) when no rung can
    serve, and propagates request errors (``PreflightError`` etc.)
    untouched."""
    options = _api.SolveOptions() if options is None else options
    resilience = ResilientOptions() if resilience is None else resilience
    rungs = _build_rungs(options, fleet=fleet)
    return _serve(problem, rungs, rungs[0][0], options, resilience,
                  lambda label, opts: _api.solve(
                      problem, opts, warm_start=warm_start))


class ResilientMatcher:
    """The compile-once/run-many analogue of :func:`resilient_solve`: one
    planned ``Matcher`` per rung (built lazily on first use, cached), the
    same guarded serving loop per call."""

    def __init__(self, problem_spec, options: _api.SolveOptions | None = None,
                 resilience: ResilientOptions | None = None, fleet=None):
        self.options = _api.SolveOptions() if options is None else options
        self.resilience = ResilientOptions() if resilience is None \
            else resilience
        self.fleet = fleet
        self._spec = problem_spec
        self._rungs = _build_rungs(self.options, fleet=fleet)
        self._matchers: dict[str, _api.Matcher] = {}

    def _matcher(self, label, opts) -> _api.Matcher:
        m = self._matchers.get(label)
        if m is None:
            m = _api.plan(self._spec, opts)
            self._matchers[label] = m
        return m

    def __call__(self, problem: _api.MatchingProblem,
                 warm_start=None) -> ResilientResult:
        return _serve(
            problem, self._rungs, self._rungs[0][0], self.options,
            self.resilience,
            lambda label, opts: self._matcher(label, opts)(
                problem, warm_start=warm_start))

    def __repr__(self):
        return (f"ResilientMatcher(rungs={[r for r, _ in self._rungs]}, "
                f"resilience={self.resilience})")
