"""Chaos harness: deterministic fault injection for the AWPM pipeline.

The acceptance bar for the robustness layer (DESIGN.md §9): every injected
fault is provably either **detected** — the pipeline raises a typed error —
or **survived** — the served result is bit-identical to the reference
backend (via fallback). Zero silent corruptions.

Fault classes and their hooks:

  exchange payload faults   drop / duplicate / corrupt_index /
                            corrupt_weight / nan_weight applied to the
                            received buffers of either stage of
                            ``core.dist.a2a_bucketed_batched`` (the
                            ``dist._EXCHANGE_TAP`` trace-time hook).
                            Detection: ``SolveOptions(exchange_check=True)``
                            conservation accounting (count + order-
                            independent checksum) -> ``ExchangeIntegrityError``.
                            Survival: ``resilient_solve`` degrades to the
                            local chain, which never touches the exchange.
  flip_converged            forces the batched AWAC convergence mask off
                            after ``count`` rounds (the
                            ``batch._CONVERGENCE_TAP`` hook) — the classic
                            "looks converged, is not" failure. Detection:
                            ``ResilientOptions(verify_convergence=True)``
                            audit (a converged result must admit no
                            augmenting 4-cycle). Survival: a single-instance
                            problem degrades to ``single._awac_loop``,
                            which the tap cannot reach.
  backend failure           ``failing_backend`` / ``failing_grid`` patch the
                            engine entry points to raise (transiently or
                            persistently). Survival: retry + degradation.
  device loss               ``runtime.elastic.fail_hosts`` masking; survival
                            via ``surviving_mesh`` replanning or the local
                            chain.
  nan input                 non-finite weights in the problem itself.
                            Detection: ``core.preflight`` (the default
                            ``on_invalid="raise"``); survival:
                            ``on_invalid="sanitize"``.

All injection is seed-deterministic (positions are chosen by rank among
the valid entries, rotated by ``seed``) and trace-time: ``inject`` swaps a
module-level tap and clears the jit caches so the faulty collective is
actually compiled in, then restores and clears again on exit.

``run_chaos_matrix`` executes the whole detect-vs-survive matrix and
returns one record per case; the chaos CI job fails if any record is not
ok. Works on any (pr, pc) grid incl. 1x1 (exchange faults need pc > 1 or
pr > 1 to have a real collective but the taps fire regardless).
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api as _api
from repro.core.dist import ExchangeIntegrityError
from repro.core.preflight import PreflightError
from repro.runtime.resilient import (
    ResilientOptions,
    TransientFault,
    VerificationError,
    resilient_solve,
    verify_result,
)

__all__ = [
    "EXCHANGE_FAULTS",
    "FaultSpec",
    "failing_backend",
    "failing_grid",
    "inject",
    "run_chaos_matrix",
]

#: payload fault kinds the exchange tap implements
EXCHANGE_FAULTS = ("drop", "duplicate", "corrupt_index", "corrupt_weight",
                   "nan_weight")


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """One deterministic fault. ``stage`` selects which exchange stage the
    payload faults hit (1 = column routing, 2 = row routing, None = both);
    ``seed`` rotates which valid entries are chosen; ``count`` is how many
    entries per instance (payload faults) or how many AWAC rounds to allow
    before forcing convergence (flip_converged)."""

    kind: str
    stage: int | None = None
    seed: int = 0
    count: int = 1

    def __post_init__(self):
        if self.kind not in EXCHANGE_FAULTS + ("flip_converged",):
            raise ValueError(f"unknown fault kind {self.kind!r}")
        if self.stage not in (None, 1, 2):
            raise ValueError(f"stage must be None, 1, or 2, got {self.stage!r}")


def _selected(valid, seed: int, count: int):
    """[B, L] bool: deterministically pick ``min(count, n_valid)`` valid
    entries per instance — by rank among valid entries, rotated by seed."""
    idx = jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1
    nv = valid.sum(axis=1, keepdims=True)
    return valid & (((idx - seed) % jnp.maximum(nv, 1)) < count)


def _exchange_tap(fault: FaultSpec):
    def tap(axis_name, outs, valid):
        names = axis_name if isinstance(axis_name, (tuple, list)) \
            else (axis_name,)
        stage = 1 if "model" in names else 2
        if fault.stage is not None and stage != fault.stage:
            return outs, valid
        sel = _selected(valid, fault.seed, fault.count)
        if fault.kind == "drop":
            return outs, valid & ~sel
        if fault.kind == "duplicate":
            b, L = valid.shape
            bix = jnp.arange(b)
            src = jnp.argmax(sel, axis=1)
            dst = jnp.argmax(~valid, axis=1)
            do = sel.any(axis=1) & (~valid).any(axis=1)
            onehot = do[:, None] & (
                jnp.arange(L)[None, :] == dst[:, None])
            outs = [jnp.where(onehot, a[bix, src][:, None], a) for a in outs]
            return outs, valid | onehot
        if fault.kind == "corrupt_index":
            outs = [jnp.where(sel, outs[0] + 1, outs[0])] + list(outs[1:])
            return outs, valid
        w = outs[-1]
        if fault.kind == "corrupt_weight":
            w = jnp.where(sel, w * jnp.float32(1.0009765625) + 1.0, w)
        else:  # nan_weight
            w = jnp.where(sel, jnp.float32(jnp.nan), w)
        return list(outs[:-1]) + [w], valid

    return tap


def _convergence_tap(fault: FaultSpec):
    def tap(active, iters):
        # force "converged" once ``count`` rounds have run
        return active & (iters < fault.count)

    return tap


@contextlib.contextmanager
def inject(fault: FaultSpec):
    """Install ``fault``'s trace-time tap for the duration of the block.
    Clears the jit caches on entry and exit so the tap is compiled in (and
    back out) — cached executables would otherwise keep serving the clean
    (or faulty) collective."""
    from repro.core import batch as _batch
    from repro.core import dist as _dist

    if fault.kind == "flip_converged":
        prev = _batch._CONVERGENCE_TAP
        _batch._CONVERGENCE_TAP = _convergence_tap(fault)
    else:
        prev = _dist._EXCHANGE_TAP
        _dist._EXCHANGE_TAP = _exchange_tap(fault)
    jax.clear_caches()
    try:
        yield
    finally:
        if fault.kind == "flip_converged":
            _batch._CONVERGENCE_TAP = prev
        else:
            _dist._EXCHANGE_TAP = prev
        jax.clear_caches()


@contextlib.contextmanager
def failing_backend(*backends, exc_type=TransientFault,
                    fail_times: int | None = None):
    """Patch the local engine entry points so any solve resolving to one of
    ``backends`` raises ``exc_type`` — persistently, or only for the first
    ``fail_times`` offending calls (a transient fault). Yields a dict whose
    ``n`` counts injected failures."""
    from repro.core import batch as _batch
    from repro.core import single as _single

    state = {"n": 0}

    def wrap(orig):
        def inner(*args, backend="auto", **kw):
            if _single.resolve_backend(backend) in backends:
                if fail_times is None or state["n"] < fail_times:
                    state["n"] += 1
                    raise exc_type(
                        f"injected {backends} backend failure "
                        f"#{state['n']}")
            return orig(*args, backend=backend, **kw)

        return inner

    orig_s, orig_b = _single._awpm, _batch._awpm_batched
    _single._awpm = wrap(orig_s)
    _batch._awpm_batched = wrap(orig_b)
    try:
        yield state
    finally:
        _single._awpm = orig_s
        _batch._awpm_batched = orig_b


@contextlib.contextmanager
def failing_grid(exc_type=TransientFault, fail_times: int | None = None):
    """Patch the distributed driver so grid dispatches raise ``exc_type``
    (persistently or for the first ``fail_times`` calls)."""
    from repro.core import dist as _dist

    state = {"n": 0}
    orig = _dist._DistBatchedAWPM.run

    def run(self, *args, **kwargs):
        if fail_times is None or state["n"] < fail_times:
            state["n"] += 1
            raise exc_type(f"injected grid engine failure #{state['n']}")
        return orig(self, *args, **kwargs)

    _dist._DistBatchedAWPM.run = run
    try:
        yield state
    finally:
        _dist._DistBatchedAWPM.run = orig


# --------------------------------------------------------------------------
# the detect-vs-survive matrix
# --------------------------------------------------------------------------


def _bit_identical(result: _api.MatchResult, ref: _api.MatchResult) -> bool:
    return (np.array_equal(np.asarray(result.mate_row),
                           np.asarray(ref.mate_row))
            and np.array_equal(np.asarray(result.mate_col),
                               np.asarray(ref.mate_col))
            and np.array_equal(np.asarray(result.weight),
                               np.asarray(ref.weight)))


def _pick_instance(n: int, avg_degree: float, min_awac_iters: int):
    """Deterministic seed scan for an instance whose reference solve needs
    at least ``min_awac_iters`` AWAC rounds (so a prematurely-flipped
    convergence mask provably leaves an augmenting 4-cycle behind). A fixed
    shared capacity keeps every candidate on one compiled executable."""
    from repro.core import graph as _graph

    cap = None
    for seed in range(200):
        for kind in ("antigreedy", "uniform"):
            g = _graph.generate(n, avg_degree=avg_degree, kind=kind,
                                seed=seed)
            real = np.asarray(g.row) < n
            if cap is None:
                cap = max(int(real.sum()) * 2, 64)
            if int(real.sum()) > cap:
                continue
            p = _api.MatchingProblem.from_coo(
                np.asarray(g.row)[real], np.asarray(g.col)[real],
                np.asarray(g.val)[real], n, capacity=cap)
            r = _api.solve(p, _api.SolveOptions(backend="reference"))
            if bool(r.perfect) and int(r.awac_iters) >= min_awac_iters:
                return p, r
    raise RuntimeError(
        f"no planted instance with >= {min_awac_iters} AWAC rounds found")


def run_chaos_matrix(pr: int = 2, pc: int = 4, n: int = 48,
                     avg_degree: float = 6.0, log=print):
    """Execute the full fault-injection matrix on a (pr, pc) fake-device
    grid. Returns a list of records ``{"fault", "mode", "ok", "detail"}`` —
    one per (fault class, detect/survive) case; the chaos CI job asserts
    every record is ok. Needs pr * pc local devices."""
    from repro.runtime import elastic

    mesh = jax.make_mesh((pr, pc), ("data", "model"))
    gopts = _api.SolveOptions(grid=mesh, exchange_check=True)
    records = []

    def record(fault, mode, ok, detail):
        records.append({"fault": fault, "mode": mode, "ok": bool(ok),
                        "detail": detail})
        log(f"[chaos] {'ok ' if ok else 'FAIL'} {fault:<24} {mode:<8} "
            f"{detail}")

    # a planted instance whose reference solve needs >= 3 AWAC rounds:
    # stopping after round 1 provably leaves an augmenting 4-cycle
    p, ref = _pick_instance(n, avg_degree, min_awac_iters=3)

    # ---- exchange payload faults: detect via conservation accounting,
    # ---- survive via degradation to the local chain ----
    for kind in EXCHANGE_FAULTS:
        for stage in (1, 2):
            fault = FaultSpec(kind, stage=stage, seed=7)
            name = f"{kind}@stage{stage}"
            with inject(fault):
                try:
                    _api.solve(p, gopts)
                    record(name, "detect", False,
                           "no ExchangeIntegrityError raised")
                except ExchangeIntegrityError:
                    record(name, "detect", True, "ExchangeIntegrityError")
            with inject(fault):
                rr = resilient_solve(p, gopts)
                ok = _bit_identical(rr.result, ref) and rr.report.degraded
                record(name, "survive", ok, rr.report.summary())

    # ---- flip_converged: detected on a batched problem (every rung shares
    # ---- the tainted batched loop), survived by a single instance (the
    # ---- single-instance loop is out of the tap's reach) ----
    fault = FaultSpec("flip_converged", count=1)
    pb = _api.MatchingProblem.stack([p, p])
    ropts = ResilientOptions(verify_convergence=True)
    with inject(fault):
        try:
            resilient_solve(pb, _api.SolveOptions(grid=mesh),
                            resilience=ropts)
            record("flip_converged", "detect", False,
                   "premature convergence not flagged")
        except VerificationError as e:
            record("flip_converged", "detect", True,
                   f"VerificationError after {len(e.report.attempts)} "
                   f"attempt(s)")
    with inject(fault):
        rr = resilient_solve(p, _api.SolveOptions(grid=mesh),
                             resilience=ropts)
        ok = _bit_identical(rr.result, ref) and rr.report.degraded
        record("flip_converged", "survive", ok, rr.report.summary())

    # ---- backend failures: transient (retry, same rung) and persistent
    # ---- (degrade down the chain), plus a dying grid engine ----
    with failing_backend("xla", fail_times=1):
        rr = resilient_solve(p)
        record("backend_transient", "survive",
               _bit_identical(rr.result, ref) and not rr.report.degraded,
               rr.report.summary())
    with failing_backend("xla"):
        rr = resilient_solve(p)
        ok = _bit_identical(rr.result, ref) \
            and rr.report.backend_used == "local reference"
        record("backend_persistent", "survive", ok, rr.report.summary())
    with failing_grid():
        rr = resilient_solve(p, _api.SolveOptions(grid=mesh))
        ok = _bit_identical(rr.result, ref) and rr.report.degraded
        record("grid_engine_down", "survive", ok, rr.report.summary())

    # ---- device loss: shrink to the surviving rows, or go local ----
    fleet = elastic.initial_fleet(mesh)
    if pr > 1:
        dead = elastic.fail_hosts(
            fleet, [np.asarray(mesh.devices)[-1, 0].id])
        rr = resilient_solve(p, _api.SolveOptions(grid=mesh), fleet=dead)
        ok = _bit_identical(rr.result, ref) \
            and "shrunk" in (rr.report.backend_used or "")
        record("device_loss_partial", "survive", ok, rr.report.summary())
    dead_all = elastic.fail_hosts(
        fleet, [r[0].id for r in np.asarray(mesh.devices).reshape(
            -1, np.asarray(mesh.devices).shape[-1])])
    rr = resilient_solve(p, _api.SolveOptions(grid=mesh), fleet=dead_all)
    ok = _bit_identical(rr.result, ref) \
        and (rr.report.backend_used or "").startswith("local")
    record("device_loss_total", "survive", ok, rr.report.summary())

    # ---- nan input: rejected by preflight, or sanitized and re-verified.
    # The NaN edge goes into a padding slot, so sanitization restores
    # exactly ``p`` and the served result must be bit-identical to ref ----
    row = np.asarray(p.row).copy()
    col = np.asarray(p.col).copy()
    val = np.asarray(p.val).copy()
    pad = np.flatnonzero(row >= n)
    row[pad[-1]], col[pad[-1]], val[pad[-1]] = 0, 0, np.nan
    p_nan = _api.MatchingProblem(row=row, col=col, val=val, n=n)
    try:
        _api.solve(p_nan, _api.SolveOptions(grid=mesh))
        record("nan_input", "detect", False, "no PreflightError raised")
    except PreflightError:
        record("nan_input", "detect", True, "PreflightError")
    rr = resilient_solve(
        p_nan, _api.SolveOptions(grid=mesh, exchange_check=True,
                                 on_invalid="sanitize"))
    ok = _bit_identical(rr.result, ref) \
        and not verify_result(p, rr.result)
    record("nan_input", "survive", ok, rr.report.summary())
    return records


def assert_all_ok(records):
    bad = [r for r in records if not r["ok"]]
    if bad:
        lines = "\n".join(
            f"  {r['fault']} [{r['mode']}]: {r['detail']}" for r in bad)
        raise AssertionError(
            f"{len(bad)} chaos case(s) neither detected nor survived:\n"
            f"{lines}")
    return records


def main(argv=None):
    """CLI entry for the CI chaos job: run the full matrix on a pr x pc
    mesh (the fake device count must be set via XLA_FLAGS before jax
    initializes) and exit non-zero on any silent corruption."""
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--pr", type=int, default=2)
    ap.add_argument("--pc", type=int, default=4)
    ap.add_argument("--n", type=int, default=48)
    args = ap.parse_args(argv)
    records = run_chaos_matrix(pr=args.pr, pc=args.pc, n=args.n)
    assert_all_ok(records)
    print(f"ALL {len(records)} CASES OK", flush=True)


if __name__ == "__main__":
    main()
