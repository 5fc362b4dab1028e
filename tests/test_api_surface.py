"""Public-API snapshot: the exported surfaces of ``repro.core``,
``repro.data``, and ``repro.solver`` are contracts — additions are
deliberate (update the snapshot in the same PR that extends the facade),
removals/renames are breaking and must not happen silently. Also guards
the facade acceptance rule: no consumer (pivot, moe, examples,
benchmarks) may call a legacy matching entry point directly anymore."""
import pathlib
import re

import repro.core as core
import repro.data as data
import repro.solver as solver_mod

REPO = pathlib.Path(__file__).resolve().parents[1]

# the one public surface (DESIGN.md §7): facade types + callables first,
# then the submodules and the graph utilities that predate the facade
EXPECTED_EXPORTS = [
    "BACKENDS",
    "BipartiteGraph",
    "DualCertificate",
    "InfeasibleProblemError",
    "MIN_GAIN",
    "MatchResult",
    "Matcher",
    "MatchingProblem",
    "ON_INVALID",
    "PreflightError",
    "PreflightReport",
    "ProblemSpec",
    "SolveOptions",
    "api",
    "batch",
    "certify",
    "dual",
    "dual_certificate",
    "from_coo",
    "generate",
    "graph",
    "matrix_suite",
    "pivot",
    "plan",
    "preflight",
    "ref",
    "single",
    "solve",
]

EXPECTED_API_EXPORTS = [
    "BACKENDS",
    "MIN_GAIN",
    "MatchResult",
    "Matcher",
    "MatchingProblem",
    "ON_INVALID",
    "ProblemSpec",
    "SolveOptions",
    "plan",
    "solve",
]


# the ingestion facade: suitesparse (opt-in network) rides next to the
# fixture loaders, never silently replacing them
EXPECTED_DATA_EXPORTS = [
    "matrices",
    "mtx",
    "suitesparse",
    "weight_transforms",
]

# the solver subsystem (DESIGN.md §12): matching-as-pivoting end to end
EXPECTED_SOLVER_EXPORTS = [
    "CsrMatrix",
    "LUFactorization",
    "LUStats",
    "PIVOTING_MODES",
    "RefineResult",
    "ScaledPivoting",
    "SolveReport",
    "awpm_pivoting",
    "from_matching",
    "identity_pivoting",
    "lu_solve_once",
    "reference_pivoting",
    "refine",
    "solve_linear_system",
    "sparse_lu",
]


def test_core_export_snapshot():
    assert sorted(core.__all__) == EXPECTED_EXPORTS
    for name in core.__all__:
        assert hasattr(core, name), f"__all__ exports missing name {name}"


def test_api_export_snapshot():
    assert sorted(core.api.__all__) == EXPECTED_API_EXPORTS
    for name in core.api.__all__:
        assert hasattr(core.api, name)
    # the facade re-exports are the same objects, not copies
    assert core.solve is core.api.solve
    assert core.MatchingProblem is core.api.MatchingProblem
    assert core.MIN_GAIN == core.single.MIN_GAIN == core.ref.MIN_GAIN


def test_data_export_snapshot():
    assert sorted(data.__all__) == EXPECTED_DATA_EXPORTS
    for name in data.__all__:
        assert hasattr(data, name)


def test_solver_export_snapshot():
    assert sorted(solver_mod.__all__) == EXPECTED_SOLVER_EXPORTS
    for name in solver_mod.__all__:
        assert hasattr(solver_mod, name)
    # the certificate accessor the solver's scaling recovery depends on
    assert callable(core.DualCertificate.potentials)


# --------------------------------------------------------------------------
# no consumer calls a legacy entry point directly anymore
# --------------------------------------------------------------------------

# the deprecated names (word-bounded, so e.g. bench_awpm_batched and
# awpm_route don't match)
_LEGACY = re.compile(
    r"\bsingle\.awpm\b|\bawpm_batched\b|\bawpm_dist_batched\b"
    r"|\bDistAWPM\b|\bDistBatchedAWPM\b|\bmake_awpm_dist_batched\b")

CONSUMER_FILES = [
    "src/repro/core/pivot.py",
    "src/repro/models/moe.py",
    *sorted(str(p.relative_to(REPO)) for p in (REPO / "examples").glob("*.py")),
    *sorted(str(p.relative_to(REPO)) for p in (REPO / "benchmarks").glob("*.py")),
]


def test_no_consumer_calls_legacy_entry_points():
    offenders = []
    for rel in CONSUMER_FILES:
        for lineno, line in enumerate(
                (REPO / rel).read_text().splitlines(), 1):
            code = line.split("#", 1)[0]
            if _LEGACY.search(code):
                offenders.append(f"{rel}:{lineno}: {line.strip()}")
    assert not offenders, (
        "legacy matching entry points must go through repro.core.api:\n"
        + "\n".join(offenders))
