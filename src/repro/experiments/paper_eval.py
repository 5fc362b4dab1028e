"""Per-matrix AWPM quality evaluation in the paper's metric (DESIGN.md §8).

The paper's claim is about REAL matrices: AWPM weights "very close to the
optimum" on SuiteSparse instances under MC64 log-scaled weights. This
module is that experiment's harness:

  - cases: checked-in Matrix Market fixtures (``tests/data/*.mtx``,
    loaded through ``repro.data.mtx`` with a per-fixture weight transform)
    plus instances of the synthetic ``core.graph.matrix_suite``;
  - sweep: every case through the ``solve()``/``Matcher`` facade across
    local backends (reference / xla / pallas, those the platform compiles)
    and device grids (in process where the attached devices hold them; on
    the CPU, larger grids in a subprocess with fake host devices, the
    tests/_subproc.py constraint; on an accelerator, skipped);
  - evidence per (case, engine): matching weight, AWAC iterations, wall
    time, the LP-dual certified ratio bound (``core.dual``), the exact
    ratio when the ``ref.exact_mwpm`` oracle is tractable, and
    bit-identity against the reference backend.

``run_eval`` RAISES on a correctness violation — an unsound certificate
(bound < exact optimum), a backend disagreeing with reference, or an
imperfect matching — so the CI docs job's ``--quick`` smoke is an
executable soundness check, not just a timing pass. Outputs: a per-matrix
markdown table under ``results/`` and ``BENCH_paper_eval.json`` at the
repo root (same row schema as every BENCH file; gated by
``benchmarks/check_regression.py``).
"""
from __future__ import annotations

import dataclasses
import datetime
import json
import os
import pathlib
import re
import subprocess
import sys
import time
from typing import Sequence

import numpy as np

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_FIXTURE_DIR = REPO_ROOT / "tests" / "data"

#: per-fixture weight transform: the paper metric (MC64 log2-scaled, lifted
#: non-negative) where magnitudes span decades; |a_ij| for the symmetric /
#: integer fixtures; pattern files are already unit-weight.
FIXTURE_TRANSFORMS = {
    "circuit8": "log2_scaled_nonneg",
    "bands6_sym": "abs",
    "mesh5_pat": None,
    "count4_int": "abs",
    "illcond9": "log2_scaled_nonneg",
    "zcoil7": "log2_scaled_nonneg",
}

# engines swept: local backends + device grids (grid rows use the Matcher
# plan()-once path with backend "auto")
LOCAL_BACKENDS = ("reference", "xla", "pallas")
GRIDS = ((1, 1), (2, 2))


def default_backends() -> tuple[str, ...]:
    """The :data:`LOCAL_BACKENDS` the current platform can compile (on a
    TPU, the Pallas sweep has no Mosaic lowering)."""
    from repro.core.single import backend_available

    return tuple(b for b in LOCAL_BACKENDS if backend_available(b))

_ROW_MARK = "PAPER_EVAL_ROW "  # subprocess -> parent protocol


@dataclasses.dataclass(frozen=True)
class EvalCase:
    """One instance to evaluate: a built problem + reporting metadata."""

    name: str
    problem: object  # MatchingProblem, single instance
    source: str  # "fixture" | "synthetic"
    transform: str  # weight metric label for the table
    nnz: int


@dataclasses.dataclass
class EvalRecord:
    """One (case, engine) measurement — a row of the per-matrix table."""

    name: str
    source: str
    transform: str
    engine: str  # "reference" | "xla" | "pallas" | "grid1x1" | "grid2x2"
    n: int
    nnz: int
    weight: float
    upper_bound: float
    ratio_bound: float | None  # certified lower bound on weight/OPT (None: no valid bound)
    ratio_exact: float | None  # vs ref.exact_mwpm when tractable
    tight: bool
    awac_iters: int
    wall_s: float
    perfect: bool
    identical_to_reference: bool
    certified_sound: bool  # bound >= exact optimum (True when no oracle ran)


def fixture_cases(fixture_dir=None) -> list[EvalCase]:
    """Load every checked-in ``.mtx`` fixture with its paper-metric
    transform (unknown files default to ``abs``)."""
    from repro.data.mtx import load_problem

    fixture_dir = pathlib.Path(fixture_dir or DEFAULT_FIXTURE_DIR)
    cases = []
    for path in sorted(fixture_dir.glob("*.mtx")):
        transform = FIXTURE_TRANSFORMS.get(path.stem, "abs")
        problem, coo = load_problem(path, transform=transform)
        cases.append(EvalCase(
            name=path.stem, problem=problem, source="fixture",
            transform=transform or "pattern", nnz=coo.nnz))
    if not cases:
        raise FileNotFoundError(f"no .mtx fixtures under {fixture_dir}")
    return cases


def extra_mtx_cases(paths) -> list[EvalCase]:
    """Cases for out-of-tree ``.mtx`` files (the ``--download``-fetched
    SuiteSparse instances). Unknown stems default to the paper metric
    (``log2_scaled_nonneg``) — these ARE the paper's instances."""
    from repro.data.mtx import load_problem

    cases = []
    for p in paths:
        path = pathlib.Path(p)
        transform = FIXTURE_TRANSFORMS.get(path.stem, "log2_scaled_nonneg")
        problem, coo = load_problem(path, transform=transform)
        cases.append(EvalCase(
            name=path.stem, problem=problem, source="suitesparse",
            transform=transform or "pattern", nnz=coo.nnz))
    return cases


def synthetic_cases(count: int = 10, n: int = 96,
                    transform=None) -> list[EvalCase]:
    """A slice of the synthetic suite (already §6.1-normalized; pass
    ``transform`` to re-measure it in another metric, e.g. the paper's
    log2-scaled one)."""
    from repro.core.api import MatchingProblem
    from repro.core.graph import matrix_suite
    from repro.data.weight_transforms import get_transform

    cases = []
    for name, g in matrix_suite(n_matrices=count, n=n):
        nnz = g.nnz
        if transform is None:
            problem = MatchingProblem.from_graph(g)
            label = "rowcol"
        else:
            mask = np.arange(g.capacity) < g.nnz
            row, col = g.row[mask], g.col[mask]
            val = get_transform(transform)(row, col, g.val[mask], g.n)
            problem = MatchingProblem.from_coo(row, col, val, g.n)
            label = transform if isinstance(transform, str) else "custom"
        cases.append(EvalCase(name=name, problem=problem, source="synthetic",
                              transform=label, nnz=nnz))
    return cases


def _exact_optimum(case: EvalCase):
    """ref.exact_mwpm on a densified instance, or None when intractable."""
    from repro.core import ref

    if not ref.HAVE_SCIPY:
        return None
    p = case.problem
    n = p.n
    row = np.asarray(p.row)
    col = np.asarray(p.col)
    val = np.asarray(p.val)
    m = (row < n) & (col < n)
    dense = np.zeros((n, n), np.float32)
    struct = np.zeros((n, n), bool)
    dense[row[m], col[m]] = val[m]
    struct[row[m], col[m]] = True
    _, opt = ref.exact_mwpm(dense, struct)
    return float(opt)


def _record(case: EvalCase, engine: str, res, wall_s: float, opt,
            ref_mate, tol: float = 1e-5) -> EvalRecord:
    from repro.core.dual import certify

    cert = certify(case.problem, res)
    mate = np.asarray(res.mate_row)
    identical = bool(np.array_equal(mate, ref_mate)) if ref_mate is not None \
        else True
    scale = max(1.0, abs(opt)) if opt is not None else 1.0
    sound = True if opt is None else \
        bool(cert.upper_bound >= opt - tol * scale)
    ratio_exact = None if opt in (None, 0.0) else float(cert.weight / opt)
    return EvalRecord(
        name=case.name, source=case.source, transform=case.transform,
        engine=engine, n=case.problem.n, nnz=case.nnz,
        weight=float(cert.weight), upper_bound=float(cert.upper_bound),
        ratio_bound=cert.ratio_bound_or(None), ratio_exact=ratio_exact,
        tight=bool(cert.tight), awac_iters=int(np.asarray(res.awac_iters)),
        wall_s=float(wall_s), perfect=bool(np.asarray(res.perfect)),
        identical_to_reference=identical, certified_sound=sound)


def _check(rec: EvalRecord) -> None:
    problems = []
    if not rec.perfect:
        problems.append("matching is not perfect")
    if not rec.certified_sound:
        problems.append(
            f"UNSOUND certificate: upper_bound={rec.upper_bound:.6f} < "
            f"exact optimum")
    if not rec.identical_to_reference:
        problems.append("result differs from the reference backend")
    if problems:
        raise AssertionError(
            f"paper_eval {rec.name} [{rec.engine}]: " + "; ".join(problems))


def _case_aux(case: EvalCase, oracle_max_n: int) -> tuple:
    """The per-case comparison baseline, computed ONCE per sweep: the exact
    optimum (when tractable) and the reference-backend mates every other
    engine must match bit-for-bit — even when 'reference' is not itself in
    the swept backends, so identical_to_reference is always a real
    comparison, never a default."""
    from repro.core.api import SolveOptions, solve

    opt = _exact_optimum(case) if case.problem.n <= oracle_max_n else None
    ref_res = solve(case.problem, SolveOptions(backend="reference"))
    return opt, np.asarray(ref_res.mate_row)


def _eval_local(case: EvalCase, backends: Sequence[str],
                aux: tuple) -> list[EvalRecord]:
    from repro.core.api import SolveOptions, solve

    opt, ref_mate = aux
    records = []
    for backend in backends:
        opts = SolveOptions(backend=backend)
        solve(case.problem, opts)  # warmup: compile outside the timing
        t0 = time.perf_counter()
        res = solve(case.problem, opts)
        np.asarray(res.mate_row)  # materialize before stopping the clock
        wall = time.perf_counter() - t0
        rec = _record(case, backend, res, wall, opt, ref_mate)
        _check(rec)
        records.append(rec)
    return records


def _cases_from_spec(spec: dict) -> list[EvalCase]:
    """Build the case list from a JSON-able spec — the same dict drives the
    in-process sweep and the fake-device subprocess, so both sides hold the
    identical (deterministic) case list."""
    cases = []
    if spec.get("fixtures", True):
        cases += fixture_cases(spec.get("fixture_dir"))
    if spec.get("extra_mtx"):
        cases += extra_mtx_cases(spec["extra_mtx"])
    if spec.get("synthetic_count", 0):
        cases += synthetic_cases(spec["synthetic_count"],
                                 spec.get("synthetic_n", 96),
                                 spec.get("synthetic_transform"))
    keep = spec.get("names")
    if keep is not None:
        cases = [c for c in cases if c.name in set(keep)]
    return cases


def _eval_grid(cases: Sequence[EvalCase], spec: dict, grid: tuple[int, int],
               oracle_max_n: int, aux_by_name: dict) -> list[EvalRecord]:
    """One grid's rows for every case. In-process on the attached devices
    when they can hold the grid (reusing the sweep's per-case
    oracle/reference baselines). Only on the CPU does a larger grid fall
    back to one subprocess with fake host devices (the
    ``--xla_force_host_platform_device_count`` must-precede-jax rule;
    baselines are recomputed child-side): this process may hold an
    accelerator, and a child must never need it, so on an accelerator the
    grid is skipped."""
    import jax

    pr, pc = grid
    if pr * pc <= jax.device_count():
        return _eval_grid_inproc(cases, grid, oracle_max_n, aux_by_name)
    if jax.default_backend() != "cpu":
        print(f"# skip grid {pr}x{pc}: only {jax.device_count()} "
              f"{jax.default_backend()} device(s) attached", flush=True)
        return []
    return _eval_grid_subproc(spec, grid, oracle_max_n, n_cases=len(cases))


def _eval_grid_inproc(cases, grid, oracle_max_n, aux_by_name=None):
    import jax

    from repro.core.api import SolveOptions, plan
    from repro.core.dist import make_mesh

    pr, pc = grid
    mesh = make_mesh((pr, pc))
    engine = f"grid{pr}x{pc}"
    records = []
    for case in cases:
        opt, ref_mate = (aux_by_name or {}).get(case.name) or \
            _case_aux(case, oracle_max_n)
        matcher = plan(case.problem, SolveOptions(grid=mesh))
        matcher(case.problem)  # warmup: partition + compile
        t0 = time.perf_counter()
        res = matcher(case.problem)
        jax.block_until_ready(res.mate_row)
        wall = time.perf_counter() - t0
        rec = _record(case, engine, res, wall, opt, ref_mate)
        _check(rec)
        records.append(rec)
    return records


_CHILD_SCRIPT = r"""
import json, sys
sys.path.insert(0, {src!r})
from repro.experiments import paper_eval

records = paper_eval._eval_grid_inproc(
    paper_eval._cases_from_spec(json.loads({spec!r})), {grid!r},
    {oracle_max_n!r})
for r in records:
    print({mark!r} + json.dumps(r.__dict__), flush=True)
"""


def _eval_grid_subproc(spec, grid, oracle_max_n, n_cases):
    pr, pc = grid
    script = _CHILD_SCRIPT.format(
        src=str(REPO_ROOT / "src"), spec=json.dumps(spec), grid=tuple(grid),
        oracle_max_n=oracle_max_n, mark=_ROW_MARK)
    env = dict(os.environ)
    # strip any inherited device-count token entirely — XLA aborts on
    # unknown flags, so the stale token can't just be renamed
    inherited = re.sub(r"--xla_force_host_platform_device_count=\S+", "",
                       env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={pr * pc} {inherited}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"  # fake host devices, never a chip
    env.setdefault("PYTHONPATH", str(REPO_ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env,
                          timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"grid {pr}x{pc} subprocess failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}")
    records = []
    for line in proc.stdout.splitlines():
        if line.startswith(_ROW_MARK):
            records.append(EvalRecord(**json.loads(line[len(_ROW_MARK):])))
    if len(records) != n_cases:
        raise RuntimeError(
            f"grid {pr}x{pc} subprocess returned {len(records)} rows for "
            f"{n_cases} cases\n--- stdout ---\n{proc.stdout}")
    return records


DEFAULT_SPEC = {"fixtures": True, "synthetic_count": 10, "synthetic_n": 96}
QUICK_SPEC = {"fixtures": True, "synthetic_count": 3, "synthetic_n": 48}


def run_eval(spec: dict | None = None,
             backends: Sequence[str] | None = None,
             grids: Sequence[tuple[int, int]] = GRIDS,
             oracle_max_n: int = 256) -> list[EvalRecord]:
    """The full sweep: every case in ``spec`` (see :func:`_cases_from_spec`;
    default :data:`DEFAULT_SPEC`) x (local ``backends``, default
    :func:`default_backends`, + device ``grids``). Raises on any soundness
    / bit-identity / perfection violation (see module docstring)."""
    if backends is None:
        backends = default_backends()
    spec = dict(DEFAULT_SPEC if spec is None else spec)
    cases = _cases_from_spec(spec)
    aux_by_name = {c.name: _case_aux(c, oracle_max_n) for c in cases}
    records = []
    for case in cases:
        records += _eval_local(case, backends, aux_by_name[case.name])
    for grid in grids:
        records += _eval_grid(cases, spec, grid, oracle_max_n, aux_by_name)
    return records


# --------------------------------------------------------------------------
# outputs: per-matrix markdown table + BENCH_paper_eval.json
# --------------------------------------------------------------------------


def _fmt_ratio(x) -> str:
    # None: dual.bound_valid was False (no certified ratio); NaN can no
    # longer reach here — DualCertificate.ratio_bound raises instead.
    if x is None or x != x:
        return "-"
    return f"{x:.4f}"


def to_markdown(records: Sequence[EvalRecord]) -> str:
    lines = [
        "# Paper evaluation: AWPM quality per matrix",
        "",
        "Generated by `experiments/run_paper_eval.py` (DESIGN.md §8). "
        "`ratio>=` is the LP-dual certified lower bound on weight/OPT "
        "(tight=True: certified optimal); `ratio` is vs the exact oracle "
        "where tractable.",
        "",
        "| matrix | src | metric | engine | n | nnz | weight | bound "
        "| ratio>= | ratio | tight | iters | ms |",
        "|---|---|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in records:
        lines.append(
            f"| {r.name} | {r.source} | {r.transform} | {r.engine} "
            f"| {r.n} | {r.nnz} | {r.weight:.4f} | {r.upper_bound:.4f} "
            f"| {_fmt_ratio(r.ratio_bound)} | {_fmt_ratio(r.ratio_exact)} "
            f"| {r.tight} | {r.awac_iters} | {r.wall_s * 1e3:.1f} |")
    return "\n".join(lines) + "\n"


def to_bench_rows(records: Sequence[EvalRecord]) -> list[dict]:
    """BENCH row schema (name / us_per_call / derived) with the
    ``certified_sound`` / ``identical_to_reference`` flags
    ``benchmarks/check_regression.py`` gates on."""
    rows = []
    for r in records:
        derived = (
            f"weight={r.weight:.4f};bound={r.upper_bound:.4f};"
            f"ratio_bound={_fmt_ratio(r.ratio_bound)};"
            f"iters={r.awac_iters};tight={r.tight};"
            f"certified_sound={r.certified_sound};"
            f"identical_to_reference={r.identical_to_reference}")
        if r.ratio_exact is not None:
            derived += f";ratio_exact={r.ratio_exact:.4f}"
        rows.append({"name": f"paper_eval_{r.name}_{r.engine}",
                     "us_per_call": round(r.wall_s * 1e6, 1),
                     "derived": derived})
    return rows


def write_outputs(records: Sequence[EvalRecord], wall_clock_s: float,
                  out_dir=None, bench_path=None, quick: bool = False):
    """Persist the markdown table (results/) + BENCH_paper_eval.json."""
    import jax

    out_dir = pathlib.Path(out_dir or REPO_ROOT / "results")
    out_dir.mkdir(parents=True, exist_ok=True)
    table = out_dir / "paper_eval.md"
    table.write_text(to_markdown(records))
    rec = {
        "suite": "paper_eval",
        "ok": True,
        "wall_clock_s": round(wall_clock_s, 3),
        "rows": to_bench_rows(records),
        "metadata": {
            "date": datetime.datetime.now(datetime.timezone.utc).isoformat(),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "quick": quick,
        },
    }
    bench_path = pathlib.Path(bench_path or REPO_ROOT / "BENCH_paper_eval.json")
    bench_path.write_text(json.dumps(rec, indent=1))
    return table, bench_path
