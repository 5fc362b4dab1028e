"""repro.core — the paper's contribution: distributed-memory approximate-weight
perfect bipartite matching (AWPM = greedy maximal -> MCM -> AWAC 4-cycles).

Public surface (DESIGN.md §7): build a :class:`MatchingProblem`, tune
:class:`SolveOptions`, call :func:`solve` (or :func:`plan` for a
compile-once/run-many :class:`Matcher`). The pre-facade entry points
(``single.awpm`` / ``batch.awpm_batched`` / ``dist.awpm_dist_batched`` and
the ``Dist*`` driver zoo) remain as bit-identical deprecation shims.
"""
from repro.core import api, batch, dual, graph, pivot, preflight, ref, single
from repro.core.api import (
    BACKENDS,
    BackendUnavailableError,
    ON_INVALID,
    Matcher,
    MatchingProblem,
    MatchResult,
    ProblemSpec,
    SolveOptions,
    plan,
    solve,
)
from repro.core.constants import MIN_GAIN
from repro.core.dual import DualCertificate, certify, dual_certificate
from repro.core.graph import BipartiteGraph, from_coo, generate, matrix_suite
from repro.core.preflight import InfeasibleProblemError, PreflightError, PreflightReport

__all__ = [
    "api",
    "batch",
    "dual",
    "graph",
    "pivot",
    "preflight",
    "ref",
    "single",
    "BACKENDS",
    "BackendUnavailableError",
    "MIN_GAIN",
    "ON_INVALID",
    "DualCertificate",
    "InfeasibleProblemError",
    "Matcher",
    "MatchingProblem",
    "MatchResult",
    "PreflightError",
    "PreflightReport",
    "ProblemSpec",
    "SolveOptions",
    "certify",
    "dual_certificate",
    "plan",
    "solve",
    "BipartiteGraph",
    "from_coo",
    "generate",
    "matrix_suite",
]
