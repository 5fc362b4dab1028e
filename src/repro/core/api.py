"""Unified AWPM facade: one problem/options/result API across single,
batched, and distributed solving.

The paper presents AWPM as ONE algorithm (greedy maximal -> MCM -> AWAC
4-cycle refinement) with one set of knobs; this module is the one public
entry point that matches that framing — the analogue of how Azad et al.
expose AWPM to SuperLU_DIST behind a single call. Three PRs of growth left
three divergent entry-point families (``single.awpm``,
``batch.awpm_batched``, ``dist.awpm_dist_batched`` plus the ``DistAWPM`` /
``DistBatchedAWPM`` / ``make_awpm_dist_batched`` factory zoo), each
threading loose COO triples and a different kwarg subset; those all remain
as deprecation shims, bit-identical, while every consumer routes through:

  - :class:`MatchingProblem` — a pytree holding the padded lex-sorted COO
    edge list ([cap] for one instance, [B, cap] for a batch) plus the
    static ``n``; constructors ``from_coo`` / ``from_graph`` / ``stack``.
  - :class:`SolveOptions` — a frozen, eagerly-validated dataclass carrying
    every knob (``max_iter``, ``min_gain``, ``backend``, ``window_steps``,
    ``grid``, ``cap``, ``a2a_caps``, ``packed``).
  - :func:`solve` — dispatches single -> batched -> distributed from the
    problem shape and grid presence, returning a :class:`MatchResult`.
  - :func:`plan` -> :class:`Matcher` — the compile-once/run-many handle:
    capacity planning (``sparse.partition.plan_block_cap``), a2a bucket
    sizing, windowed-search depth pinning, and the distributed engine
    construction all happen at plan time; the XLA compile itself lands on
    the first call (standard jit) and every later call reuses that one
    executable.

Dispatch rules (DESIGN.md §7):

  ===========  =========  =============================================
  problem      grid       engine
  ===========  =========  =============================================
  [cap]        None       ``single._awpm``        (one instance)
  [B, cap]     None       ``batch._awpm_batched`` (one dispatch, B lanes)
  [cap]        GridSpec   distributed-batched engine, lifted to B=1
  [B, cap]     GridSpec   ``dist._DistBatchedAWPM`` (one shard_map dispatch)
  ===========  =========  =============================================

Every route is bit-identical per instance to every other (the engines are
pinned to each other by the differential suites), so dispatch is purely a
performance decision.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import batch as _batch
from repro.core import graph as _graph
from repro.core import preflight as _preflight
from repro.core import single as _single
from repro.core import telemetry
from repro.core.constants import MIN_GAIN
from repro.core.single import MatchState
from repro.sparse.csr import window_depth

#: every backend ``SolveOptions`` accepts. "auto" is the fused XLA sweep
#: ("xla") locally and the "fused" exchange+windowed-join engine on a grid.
#: "reference" is the seed bit-exactness oracle. "fused" is
#: distributed-only; "xla" with a grid requires the 1x1 grid (the block is
#: the whole instance).
BACKENDS = ("auto", "reference", "xla", "fused")

#: ``SolveOptions.on_invalid`` policies (see ``core.preflight``).
ON_INVALID = ("raise", "sanitize", "degrade")

__all__ = [
    "BACKENDS",
    "MIN_GAIN",
    "ON_INVALID",
    "MatchResult",
    "Matcher",
    "MatchingProblem",
    "ProblemSpec",
    "SolveOptions",
    "plan",
    "solve",
]


# --------------------------------------------------------------------------
# problem
# --------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)  # eq=False: array fields —
# identity semantics keep == and hash() usable (pytree-dataclass convention)
class MatchingProblem:
    """One (or a batch of) heavy-weight perfect-matching instance(s).

    ``row``/``col``/``val`` follow the repo-wide padded COO convention:
    lex-sorted by (row, col) per instance, padding entries (n, n, 0),
    square n x n. Shapes are [cap] (single instance) or [B, cap] (a batch
    sharing ``n``). Direct construction assumes that convention; use
    ``from_coo`` to sort/pad raw triples, ``from_graph`` for a
    ``BipartiteGraph``, and ``stack`` to batch instances of mixed nnz.

    Registered as a jax pytree (leaves row/col/val, static ``n``) so a
    problem can cross jit boundaries whole.
    """

    row: Any  # [cap] or [B, cap] int32
    col: Any  # same shape as row
    val: Any  # same shape, float32
    n: int

    def __post_init__(self):
        shp = np.shape(self.row)
        if np.shape(self.col) != shp or np.shape(self.val) != shp:
            raise ValueError(
                f"row/col/val shapes differ: {shp}, {np.shape(self.col)}, "
                f"{np.shape(self.val)}")
        if len(shp) not in (1, 2):
            raise ValueError(
                f"expected [cap] or [B, cap] edge arrays, got shape {shp}")

    # ---- pytree protocol ----
    def tree_flatten(self):
        return (self.row, self.col, self.val), self.n

    @classmethod
    def tree_unflatten(cls, n, leaves):
        # bypass __post_init__: transforms may rebuild with placeholder
        # leaves that have no shape
        obj = object.__new__(cls)
        object.__setattr__(obj, "row", leaves[0])
        object.__setattr__(obj, "col", leaves[1])
        object.__setattr__(obj, "val", leaves[2])
        object.__setattr__(obj, "n", n)
        return obj

    # ---- shape queries ----
    @property
    def is_batched(self) -> bool:
        return len(np.shape(self.row)) == 2

    @property
    def batch_size(self) -> int | None:
        """B for a batched problem, None for a single instance."""
        shp = np.shape(self.row)
        return int(shp[0]) if len(shp) == 2 else None

    @property
    def cap(self) -> int:
        """Padded edge capacity per instance."""
        return int(np.shape(self.row)[-1])

    @property
    def spec(self) -> "ProblemSpec":
        return ProblemSpec(n=int(self.n), cap=self.cap,
                           batch=self.batch_size)

    # ---- constructors ----
    @classmethod
    def from_coo(cls, row, col, val, n: int,
                 capacity: int | None = None) -> "MatchingProblem":
        """Sort raw COO triples lexicographically and pad to ``capacity``
        (rounded up to the repo-wide alignment when None)."""
        g = _graph.from_coo(row, col, val, n, capacity=capacity)
        return cls.from_graph(g)

    @classmethod
    def from_graph(cls, g: _graph.BipartiteGraph) -> "MatchingProblem":
        return cls(row=g.row, col=g.col, val=g.val, n=g.n)

    @classmethod
    def stack(cls, items: Sequence[Any]) -> "MatchingProblem":
        """Pad instances (``BipartiteGraph``s or single-instance problems)
        of arbitrary per-instance nnz — but shared ``n`` — into one batched
        [B, cap] problem. Subsumes ``core.batch.stack_graphs``."""
        if not items:
            raise ValueError("stack() needs at least one instance")
        gs = []
        for it in items:
            if isinstance(it, _graph.BipartiteGraph):
                gs.append(it)
            elif isinstance(it, MatchingProblem):
                if it.is_batched:
                    raise ValueError(
                        "stack() takes single instances; got a batched "
                        f"problem of B={it.batch_size}")
                r = np.asarray(it.row, np.int32)
                gs.append(_graph.BipartiteGraph(
                    n=it.n, nnz=int((r < it.n).sum()), row=r,
                    col=np.asarray(it.col, np.int32),
                    val=np.asarray(it.val, np.float32)))
            else:
                raise TypeError(
                    f"stack() takes BipartiteGraphs or MatchingProblems, "
                    f"got {type(it).__name__}")
        row, col, val = _batch.stack_graphs(gs)
        return cls(row=row, col=col, val=val, n=gs[0].n)


@dataclasses.dataclass(frozen=True)
class ProblemSpec:
    """Static shape signature of a :class:`MatchingProblem` — what
    :func:`plan` specializes a :class:`Matcher` to."""

    n: int
    cap: int
    batch: int | None = None

    def __post_init__(self):
        # accept (and normalize away) numpy integers — n/cap/batch routinely
        # come off array shapes
        for name in ("n", "cap"):
            object.__setattr__(
                self, name,
                _as_int(f"{name} must be a positive int", getattr(self, name)))
        if self.batch is not None:
            object.__setattr__(
                self, "batch",
                _as_int("batch must be None or a positive int", self.batch))


# --------------------------------------------------------------------------
# options
# --------------------------------------------------------------------------


def _as_int(message: str, v, minimum: int = 1) -> int:
    """Validate an integral knob (python or numpy int, bool excluded,
    >= minimum) and normalize it to a plain int."""
    if isinstance(v, bool) or not isinstance(v, (int, np.integer)) \
            or v < minimum:
        raise ValueError(f"{message}, got {v!r}")
    return int(v)


def _as_grid_spec(grid):
    """Normalize Mesh | GridSpec -> validated GridSpec (clear errors)."""
    from repro.core.dist import GridSpec  # local: core stays light to import

    if isinstance(grid, GridSpec):
        spec = grid
    elif isinstance(grid, jax.sharding.Mesh):
        spec = GridSpec(grid)
    else:
        raise ValueError(
            f"grid must be a jax.sharding.Mesh or repro.core.dist.GridSpec, "
            f"got {type(grid).__name__}")
    have = tuple(spec.mesh.axis_names)
    missing = [a for a in (*spec.row_axes, spec.col_axis) if a not in have]
    if missing:
        raise ValueError(
            f"bad grid shape: mesh axes {have} are missing the process-grid "
            f"axes {tuple(missing)} (row_axes={spec.row_axes}, "
            f"col_axis={spec.col_axis!r})")
    return spec


@dataclasses.dataclass(frozen=True)
class SolveOptions:
    """Every AWPM knob, validated eagerly at construction.

    max_iter      AWAC round budget (>= 0; 0 skips refinement entirely).
    min_gain      minimum 4-cycle gain to count as augmenting (paper eps).
    backend       one of :data:`BACKENDS`; "auto" picks per dispatch target.
    window_steps  windowed-search depth override (None = measured/derived;
                  extra depth never changes results, and an undersized
                  override is clamped up to the measured need).
    grid          None (local) or a Mesh / ``core.dist.GridSpec`` — presence
                  selects the distributed engine.
    cap           distributed per-block edge capacity override (None = true
                  block occupancy via ``sparse.partition.plan_block_cap``;
                  too small raises "refusing to truncate" at partition
                  time — edges are never dropped silently).
    a2a_caps      distributed bucket capacities for the two exchange stages
                  (None = provably drop-free ``safe_a2a_caps``).
    packed        pack the distributed exchanges into one collective each.
    on_invalid    policy for degenerate input (``core.preflight``):
                  "raise" rejects fatal issues (non-finite weights,
                  duplicate edges) and infeasible instances with a typed
                  error; "sanitize" repairs the data (drop non-finite
                  edges, merge duplicates keep-max) but still raises on
                  infeasibility; "degrade" additionally returns the maximal
                  imperfect matching (``perfect=False``) with the diagnosis
                  attached instead of raising. All three short-circuit AWAC
                  on infeasible instances (a 4-cycle rotation can never
                  raise cardinality, so the budget would be pure waste).
    exchange_check  distributed-only: conserve-count + checksum accounting
                  across the two-stage exchange each AWAC round; any
                  drop/duplicate/corruption raises
                  ``core.dist.ExchangeIntegrityError``.
    """

    max_iter: int = 1000
    min_gain: float = MIN_GAIN
    backend: str = "auto"
    window_steps: int | None = None
    grid: Any = None
    cap: int | None = None
    a2a_caps: tuple[int, int] | None = None
    packed: bool = False
    on_invalid: str = "raise"
    exchange_check: bool = False

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}: expected one of "
                f"{BACKENDS}")
        if self.on_invalid not in ON_INVALID:
            raise ValueError(
                f"unknown on_invalid policy {self.on_invalid!r}: expected "
                f"one of {ON_INVALID}")
        object.__setattr__(
            self, "max_iter",
            _as_int("max_iter must be a non-negative int", self.max_iter,
                    minimum=0))
        if not math.isfinite(float(self.min_gain)) or float(self.min_gain) < 0:
            # negative values would admit zero/negative-gain 4-cycles and
            # let AWAC churn tie swaps for the whole max_iter budget
            raise ValueError(
                f"min_gain must be finite and >= 0, got {self.min_gain!r}")
        if self.window_steps is not None:
            object.__setattr__(
                self, "window_steps",
                _as_int("window_steps must be None or a positive int",
                        self.window_steps))
        if self.cap is not None:
            object.__setattr__(
                self, "cap",
                _as_int("cap must be None or a positive per-block edge "
                        "capacity", self.cap))
        if self.a2a_caps is not None:
            caps = tuple(self.a2a_caps)
            if len(caps) != 2:
                raise ValueError(
                    f"a2a_caps must be two positive ints (stage-1, stage-2 "
                    f"bucket capacities), got {self.a2a_caps!r}")
            caps = tuple(
                _as_int("a2a_caps must be two positive ints", c)
                for c in caps)
            object.__setattr__(self, "a2a_caps", caps)
        if self.grid is not None:
            spec = _as_grid_spec(self.grid)
            object.__setattr__(self, "grid", spec)
            if self.backend == "xla" and (spec.pr, spec.pc) != (1, 1):
                raise ValueError(
                    f"backend {self.backend!r} routes through the local "
                    f"fused sweep and needs the 1x1 grid, got "
                    f"{spec.pr}x{spec.pc}")
        else:
            if self.backend == "fused":
                raise ValueError(
                    "backend 'fused' is the distributed exchange engine and "
                    "requires SolveOptions.grid")
            for name in ("cap", "a2a_caps"):
                if getattr(self, name) is not None:
                    raise ValueError(
                        f"{name} is a distributed capacity knob and "
                        f"requires SolveOptions.grid")
            if self.packed:
                raise ValueError(
                    "packed is a distributed exchange knob and requires "
                    "SolveOptions.grid")
            if self.exchange_check:
                raise ValueError(
                    "exchange_check audits the distributed two-stage "
                    "exchange and requires SolveOptions.grid")

    def _dist_backend(self) -> str:
        return "fused" if self.backend == "auto" else self.backend


# --------------------------------------------------------------------------
# result
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ExecutionInfo:
    """How a solve actually executed — the honest dispatch record.

    ``backend``: the concrete engine that ran (never "auto").
    ``source``: how it was chosen — "explicit" (user-pinned) or "default"
    (``"auto"``: the fused XLA sweep locally, the fused engine on a grid).
    """

    backend: str
    source: str
    #: True when the solve was seeded from previous mates (warm-start
    #: rematching) instead of running greedy + MCM from scratch.
    warm_started: bool = False


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)  # eq=False: see MatchingProblem
class MatchResult:
    """Matching produced by :func:`solve` / a :class:`Matcher`.

    Single instance: ``mate_row``/``mate_col`` are [n + 1] (sentinel slot n;
    ``mate_row[j]`` = row matched to column j), ``weight``/``awac_iters``/
    ``perfect`` scalars. Batched: leading B on everything.

    ``diagnosis`` is a ``core.preflight.PreflightReport`` (or None) when
    preflight found issues worth surfacing — always present on a degraded
    (``perfect=False``) result, never on a clean solve. ``execution`` is an
    :class:`ExecutionInfo` recording the engine that actually ran (resolved
    backend and how it was chosen). Both ride as pytree
    aux_data (static).
    """

    mate_row: Any  # [n+1] or [B, n+1] int32; sentinel n = unmatched
    mate_col: Any  # [n+1] or [B, n+1] int32
    weight: Any  # matched-edge weight sum, f32
    awac_iters: Any  # AWAC rounds until convergence, i32
    perfect: Any  # bool: every column matched
    diagnosis: Any = None  # PreflightReport | None (static, host-side only)
    execution: Any = None  # ExecutionInfo | None (static)

    def tree_flatten(self):
        return (self.mate_row, self.mate_col, self.weight, self.awac_iters,
                self.perfect), (self.diagnosis, self.execution)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        diagnosis, execution = aux
        return cls(*leaves, diagnosis=diagnosis, execution=execution)


def _result(state: MatchState, iters, n: int, batched: bool) -> MatchResult:
    with telemetry.span("repro.result"):
        if batched:
            weight = _batch.matching_weight_batched(state, n)
            perfect = _batch.is_perfect_batched(state, n)
        else:
            weight = _single.matching_weight(state, n)
            perfect = _single.is_perfect(state, n)
    return MatchResult(mate_row=state.mate_row, mate_col=state.mate_col,
                       weight=weight, awac_iters=iters, perfect=perfect)


# --------------------------------------------------------------------------
# solve
# --------------------------------------------------------------------------


def _check_types(problem, options):
    if not isinstance(problem, MatchingProblem):
        raise TypeError(
            f"solve() takes a MatchingProblem (see from_coo/from_graph/"
            f"stack), got {type(problem).__name__}")
    if not isinstance(options, SolveOptions):
        raise TypeError(
            f"options must be SolveOptions, got {type(options).__name__}")


def _is_traced(problem: MatchingProblem) -> bool:
    return any(isinstance(x, jax.core.Tracer)
               for x in (problem.row, problem.col, problem.val))


def _record(problem: MatchingProblem):
    """The solve's telemetry record, with its root span ``repro.solve``;
    none for a problem traced under an outer jit."""
    if _is_traced(problem):
        return contextlib.nullcontext()
    return telemetry.record()


def _apply_preflight(problem: MatchingProblem, options: SolveOptions):
    """Host-side input screening per ``options.on_invalid``. Returns the
    (possibly sanitized) problem and the report to carry into
    :func:`_finish` — or (problem, None) under a trace, where host
    inspection is impossible (the in-engine AWAC short-circuit still
    protects infeasible instances from burning the round budget)."""
    if _is_traced(problem):
        return problem, None
    with telemetry.span("repro.preflight"):
        report = _preflight.preflight(problem)
    if report.fatal:
        if options.on_invalid == "raise":
            raise _preflight.PreflightError(
                report,
                f"preflight rejected the problem: {report.summary()}. Pass "
                f"SolveOptions(on_invalid='sanitize') to repair, or "
                f"'degrade' to also accept infeasible instances.")
        with telemetry.span("repro.preflight"):
            problem, report = _preflight.sanitize(problem)
    if report.structural and options.on_invalid == "raise":
        # empty rows/columns make a perfect matching impossible — under the
        # strict policy that is an error, and it is known before solving
        raise _preflight.InfeasibleProblemError(
            report,
            f"problem has no perfect matching: {report.summary()}. Pass "
            f"SolveOptions(on_invalid='degrade') for the maximal matching.")
    return problem, report


def _finish(problem: MatchingProblem, result: MatchResult,
            options: SolveOptions, report) -> MatchResult:
    """Post-solve policy: attach the preflight diagnosis, and on an
    imperfect result either raise (raise/sanitize policies) or return the
    degraded matching with the deficiency folded into the diagnosis."""
    if isinstance(result.perfect, jax.core.Tracer):
        return result
    with telemetry.span("repro.finish"):
        perfect = bool(np.asarray(result.perfect).all())
    if perfect:
        if report is not None and report.issues:
            return dataclasses.replace(result, diagnosis=report)
        return result
    report = _preflight.deficiency_from_mates(
        result.mate_row, problem.n, report, batched=problem.is_batched)
    if options.on_invalid != "degrade":
        raise _preflight.InfeasibleProblemError(
            report,
            f"problem has no perfect matching: {report.summary()}. Pass "
            f"SolveOptions(on_invalid='degrade') for the maximal matching.")
    return dataclasses.replace(result, diagnosis=report)


def _execution_info(options: SolveOptions) -> ExecutionInfo:
    """What will actually run, for ``MatchResult.execution``: the pinned
    backend, or for ``"auto"`` the fused XLA sweep locally and the fused
    engine on a grid."""
    backend = _single.resolve_backend(options.backend) \
        if options.grid is None else options._dist_backend()
    return ExecutionInfo(backend=backend, source="explicit"
                         if options.backend != "auto" else "default")


def _warm_mates(problem: MatchingProblem, warm_start):
    """Normalize a warm-start seed to (mate_row, mate_col) arrays matching
    the problem's batchedness ([n]/[n + 1] for a single instance, leading B
    for a batch). Accepts a previous :class:`MatchResult` or a
    (mate_row, mate_col) pair. A seed whose shape cannot belong to this
    problem raises ValueError — the serving tier catches that and falls
    back to the cold path; entry *values* are never validated here (the
    engine-side repair unmatches every stale/garbage pair)."""
    if isinstance(warm_start, MatchResult):
        mr, mc = warm_start.mate_row, warm_start.mate_col
    elif isinstance(warm_start, (tuple, list)) and len(warm_start) == 2:
        mr, mc = warm_start
    else:
        raise TypeError(
            f"warm_start must be a MatchResult or a (mate_row, mate_col) "
            f"pair, got {type(warm_start).__name__}")
    n = problem.n
    shp = np.shape(mr)
    if np.shape(mc) != shp:
        raise ValueError(
            f"warm_start mate arrays disagree: {shp} vs {np.shape(mc)}")
    if problem.is_batched:
        want = [(problem.batch_size, n), (problem.batch_size, n + 1)]
    else:
        want = [(n,), (n + 1,)]
    if shp not in want:
        raise ValueError(
            f"warm_start shape {shp} does not fit the problem (expected "
            f"one of {want}; stale seeds from a different n/batch must be "
            f"discarded, not repaired)")
    return mr, mc


def solve(problem: MatchingProblem,
          options: SolveOptions | None = None, *,
          warm_start=None) -> MatchResult:
    """Run the full AWPM pipeline (greedy maximal -> MCM -> AWAC) on
    ``problem``, dispatching on its shape and ``options.grid`` (see the
    module docstring table). Returns a :class:`MatchResult`; bit-identical
    per instance on every route and backend.

    ``warm_start`` (a previous :class:`MatchResult` or a (mate_row,
    mate_col) pair) seeds the pipeline from an earlier matching instead of
    greedy + MCM from scratch: stale pairs are repaired against the current
    edge list, a bounded MCM top-up closes any seed deficiency, and AWAC
    runs from there (DESIGN.md §11). Seeding never changes the contract —
    the result is a perfect matching of THIS problem — and a seed that is
    already an AWAC fixed point of the same problem is returned
    bit-identically."""
    options = SolveOptions() if options is None else options
    _check_types(problem, options)
    with _record(problem):
        return _solve(problem, options, warm_start)


def _solve(problem: MatchingProblem, options: SolveOptions,
           warm_start) -> MatchResult:
    """:func:`solve` inside its telemetry record."""
    warm = None if warm_start is None else _warm_mates(problem, warm_start)
    problem, report = _apply_preflight(problem, options)
    if options.grid is not None:
        result = _solve_dist(problem, options, warm=warm)
    elif problem.is_batched:
        if warm is None:
            state, iters = _batch._awpm_batched(
                problem.row, problem.col, problem.val, problem.n,
                max_iter=options.max_iter, min_gain=options.min_gain,
                backend=options.backend, window_steps=options.window_steps,
                degrade_infeasible=True)
        else:
            state, iters = _batch._awpm_batched_from_state(
                problem.row, problem.col, problem.val, problem.n,
                warm[0], warm[1], max_iter=options.max_iter,
                min_gain=options.min_gain, backend=options.backend,
                window_steps=options.window_steps, degrade_infeasible=True)
        result = _result(state, iters, problem.n, batched=True)
    else:
        if warm is None:
            state, iters = _single._awpm(
                problem.row, problem.col, problem.val, problem.n,
                max_iter=options.max_iter, min_gain=options.min_gain,
                backend=options.backend, window_steps=options.window_steps,
                degrade_infeasible=True)
        else:
            # lift to B=1: the batched engine is pinned bit-identical per
            # instance to the single-instance one, so the lift is purely
            # a code-path economy (one warm engine, not two)
            wmr, wmc = (jnp.asarray(x)[None] for x in warm)
            bstate, biters = _batch._awpm_batched_from_state(
                problem.row[None], problem.col[None], problem.val[None],
                problem.n, wmr, wmc, max_iter=options.max_iter,
                min_gain=options.min_gain, backend=options.backend,
                window_steps=options.window_steps, degrade_infeasible=True)
            state = MatchState(*(x[0] for x in bstate))
            iters = biters[0]
        result = _result(state, iters, problem.n, batched=False)
    result = dataclasses.replace(
        result, execution=dataclasses.replace(
            _execution_info(options), warm_started=warm is not None))
    return _finish(problem, result, options, report)


def _solve_dist(problem: MatchingProblem, options: SolveOptions,
                driver=None, warm=None) -> MatchResult:
    """Grid dispatch: one distributed-batched shard_map dispatch (a single
    instance is lifted to B=1 — still bit-identical, the batched engine is
    pinned per instance to the single-instance one)."""
    from repro.core import dist as _dist

    if any(isinstance(x, jax.core.Tracer)
           for x in (problem.row, problem.col, problem.val)):
        raise TypeError(
            "the distributed route partitions the edge list on the host and "
            "cannot run under jit — call solve()/Matcher with grid= outside "
            "jit (the local routes trace fine)")
    row = np.asarray(problem.row)
    col = np.asarray(problem.col)
    val = np.asarray(problem.val)
    batched = problem.is_batched
    if not batched:
        row, col, val = row[None], col[None], val[None]
    state0 = None
    if warm is not None:
        # warm start on a grid: the cheap host-side phases (seed repair +
        # MCM top-up + dual build) run on the local batched engine, then
        # ONE distributed dispatch runs the AWAC phase from that state
        # (the driver's from_state entry, DESIGN.md §5)
        from repro.sparse.csr import batched_row_ptr_from_sorted

        wmr, wmc = warm
        if not batched:
            wmr, wmc = jnp.asarray(wmr)[None], jnp.asarray(wmc)[None]
        jrow, jcol, jval = telemetry.call("repro.device_put", jax.device_put,
                                          (row, col, val))
        with telemetry.span("repro.window_depth"):
            ws = _batch._resolve_window_steps_batched(
                jrow, problem.n, options.window_steps)
        row_ptr = telemetry.call("repro.row_ptr", batched_row_ptr_from_sorted,
                                 jrow, problem.n)
        wmr, wmc = _batch._normalize_mates_batched(
            wmr, wmc, row.shape[0], problem.n)
        wmr, wmc = _batch.warm_mates_batched(
            jrow, jcol, jval, row_ptr, problem.n, wmr, wmc, ws)
        state0 = _batch._state_from_mates_windowed(
            jrow, jcol, jval, row_ptr, problem.n, wmr, wmc, ws)
    if driver is None:
        driver = _dist._DistBatchedAWPM(
            options.grid, problem.n, cap=options.cap,
            a2a_caps=options.a2a_caps, max_iter=options.max_iter,
            min_gain=options.min_gain, packed=options.packed,
            backend=options._dist_backend(),
            window_steps=options.window_steps,
            degrade_infeasible=True,
            exchange_check=options.exchange_check)
    state, iters, aux = driver.run(row, col, val, state=state0)
    aux = np.asarray(aux)
    # with exchange_check the engine psums a [dropped, integrity] pair per
    # run; otherwise aux is the plain global dropped counter
    dropped = int(aux[0]) if aux.ndim else int(aux)
    integrity = int(aux[1]) if aux.ndim else 0
    if integrity != 0:
        raise _dist.ExchangeIntegrityError(
            f"exchange integrity check failed on {integrity} AWAC round(s): "
            f"payloads received across the two-stage all_to_all do not "
            f"match what was sent (count or checksum mismatch). The "
            f"exchange lost, duplicated, or corrupted data; the result "
            f"cannot be trusted.")
    # only user-overridden a2a_caps can drop (the safe_a2a_caps default is
    # provably drop-free); a drop breaks the bit-identity contract, so it
    # is an error here, never a silent degradation
    if dropped != 0:
        raise _dist.ExchangeIntegrityError(
            f"{dropped} exchange requests were dropped by the "
            f"user-supplied a2a_caps={options.a2a_caps}: the result would "
            f"not be bit-identical to the local engines. Raise the bucket "
            f"capacities or leave a2a_caps=None for the drop-free default.")
    if not batched:
        state = MatchState(*(x[0] for x in state))
        iters = iters[0]
    return _result(state, iters, problem.n, batched)


# --------------------------------------------------------------------------
# plan: the compile-once/run-many Matcher
# --------------------------------------------------------------------------


class Matcher:
    """Solve handle specialized to one :class:`ProblemSpec` + options.

    Replaces the ``DistAWPM`` / ``DistBatchedAWPM`` /
    ``make_awpm_dist_batched`` factory zoo: all per-spec planning happens
    ONCE here — distributed per-block capacity (true occupancy via
    ``plan_block_cap`` when a prototype problem is given, the provable
    worst-case bound otherwise), drop-free a2a bucket capacities, the
    pinned windowed-search depth, and the block-level engine construction.
    The XLA compile lands on the first ``matcher(problem)`` call (standard
    jit) and every later call reuses that one executable. Construct via
    :func:`plan`.
    """

    def __init__(self, problem_spec: ProblemSpec, options: SolveOptions,
                 prototype: MatchingProblem | None = None):
        self.problem_spec = problem_spec
        self.options = options
        grid = options.grid
        self._driver = None
        if grid is None:
            # pinned local search depth: covers any row (<= min(cap, n)
            # entries), and extra depth never changes a search result. A
            # user override below that bound is lifted to it, so the pin
            # stays >= any measured need and every call keys one compiled
            # executable.
            bound = window_depth(min(problem_spec.cap, problem_spec.n))
            self._window_steps = max(options.window_steps or 0, bound)
            self.block_cap = None
            self.a2a_caps = None
            return

        from repro.core import dist as _dist
        from repro.sparse.partition import plan_block_cap

        n, pr, pc = problem_spec.n, grid.pr, grid.pc
        if options.cap is not None:
            self.block_cap = options.cap
        elif prototype is not None:
            self.block_cap = plan_block_cap(
                np.asarray(prototype.row), np.asarray(prototype.col),
                n, pr, pc)
        else:
            # worst-case occupancy: a block never holds more than its dense
            # extent nor more than the instance's whole edge list
            br, bc = -(-n // pr), -(-n // pc)
            self.block_cap = max(8, min(problem_spec.cap, br * bc))
        self.a2a_caps = options.a2a_caps or _dist.safe_a2a_caps(
            self.block_cap, pr, pc)
        # one depth formula (csr.window_depth) for plan-time pin and
        # run-time measurement, and the pin is lifted to the block bound:
        # pin >= measured always, so run() keeps the pin and the first
        # serving call hits the plan-time engine cache entry
        self._window_steps = max(options.window_steps or 0,
                                 window_depth(self.block_cap))
        self._driver = _dist._DistBatchedAWPM(
            grid, n, cap=self.block_cap, a2a_caps=self.a2a_caps,
            max_iter=options.max_iter, min_gain=options.min_gain,
            packed=options.packed, backend=options._dist_backend(),
            window_steps=self._window_steps,
            degrade_infeasible=True, exchange_check=options.exchange_check)
        # materialize the block-level engine now (plan-time, not per call;
        # the XLA compile itself still lands on the first call); the call
        # form mirrors _DistBatchedAWPM.run exactly so the lru_cache key
        # matches and the first serving call is a cache hit
        _dist._make_awpm_dist_batched(
            grid, n, problem_spec.batch or 1, self.block_cap, self.a2a_caps,
            options.max_iter, options.min_gain, packed=options.packed,
            backend=options._dist_backend(), window_steps=self._window_steps,
            from_state=False, degrade_infeasible=True,
            exchange_check=options.exchange_check)

    def _check(self, problem: MatchingProblem):
        spec = self.problem_spec
        if not isinstance(problem, MatchingProblem):
            raise TypeError(
                f"Matcher takes a MatchingProblem, got "
                f"{type(problem).__name__}")
        if problem.n != spec.n or problem.batch_size != spec.batch:
            raise ValueError(
                f"problem (n={problem.n}, batch={problem.batch_size}) does "
                f"not match the planned spec (n={spec.n}, "
                f"batch={spec.batch})")
        if problem.cap != spec.cap:
            raise ValueError(
                f"problem cap {problem.cap} != planned cap {spec.cap} "
                f"(the plan is shape-specialized; re-plan() or pad to the "
                f"planned capacity)")

    def __call__(self, problem: MatchingProblem,
                 warm_start=None) -> MatchResult:
        self._check(problem)
        opts = self.options
        if self._driver is not None:
            warm = None if warm_start is None \
                else _warm_mates(problem, warm_start)
            try:
                with _record(problem):
                    problem, report = _apply_preflight(problem, opts)
                    result = _solve_dist(problem, opts, driver=self._driver,
                                         warm=warm)
                    if result.execution is not None:
                        result = dataclasses.replace(
                            result, execution=dataclasses.replace(
                                result.execution,
                                warm_started=warm is not None))
                    return _finish(problem, result, opts, report)
            except ValueError as e:
                if "refusing to truncate" not in str(e):
                    raise
                # a prototype-planned capacity is the prototype's TRUE
                # occupancy (zero headroom) — denser same-spec data needs a
                # bigger plan, not the partition-internal advice
                raise ValueError(
                    f"problem exceeds the planned per-block capacity "
                    f"(block_cap={self.block_cap}): {e}. plan() again with "
                    f"a denser prototype, or pass SolveOptions(cap=...) "
                    f"with headroom for the serving workload.") from e
        pinned = dataclasses.replace(opts, window_steps=self._window_steps)
        return solve(problem, pinned, warm_start=warm_start)

    def __repr__(self):
        mode = "local" if self._driver is None else (
            f"grid {self.options.grid.pr}x{self.options.grid.pc}, "
            f"block_cap={self.block_cap}, a2a_caps={self.a2a_caps}")
        return (f"Matcher(n={self.problem_spec.n}, cap={self.problem_spec.cap}, "
                f"batch={self.problem_spec.batch}, "
                f"backend={self.options.backend!r}, {mode}, "
                f"window_steps={self._window_steps})")


def plan(problem_spec: ProblemSpec | MatchingProblem,
         options: SolveOptions | None = None) -> Matcher:
    """Build a :class:`Matcher` for ``problem_spec`` (a :class:`ProblemSpec`
    or a prototype :class:`MatchingProblem` — the latter lets distributed
    capacity planning measure TRUE block occupancy instead of the
    worst-case bound). Plan-time work: capacity + bucket planning, search
    depth pinning, engine construction. Call-time work: partition + one
    dispatch (the XLA compile lands on the first call and is reused by
    every later one)."""
    options = SolveOptions() if options is None else options
    if not isinstance(options, SolveOptions):
        raise TypeError(
            f"options must be SolveOptions, got {type(options).__name__}")
    prototype = None
    if isinstance(problem_spec, MatchingProblem):
        prototype = problem_spec
        problem_spec = problem_spec.spec
    elif not isinstance(problem_spec, ProblemSpec):
        raise TypeError(
            f"plan() takes a ProblemSpec or a prototype MatchingProblem, "
            f"got {type(problem_spec).__name__}")
    return Matcher(problem_spec, options, prototype=prototype)
