"""Benchmark regression gate (CI satellite): compare a freshly measured
BENCH_*.json (kernels in the bench-gate job, paper_eval in the docs job)
against the committed baseline.

Two checks, per row name present in BOTH files:
  1. correctness flags (``weight_identical=…`` / ``weights_identical=…`` /
     ``identical_to_batched=…`` / ``identical_to_local=…`` /
     ``identical_to_reference=…`` / ``certified_sound=…`` in the derived
     field) must still be True —
     a False here means an engine stopped agreeing with its oracle (or a
     dual certificate stopped bounding the optimum), which is a
     correctness failure no matter how fast it got;
  2. per-row throughput must not regress by more than ``--factor`` (default
     2.5x; shared-runner wall clocks are noisy, so the gate only catches
     step-function regressions, not percent-level drift).

Rows that exist only on one side are reported but never fail the gate
(benches grow new rows every PR). Exits 1 on any violation.

Usage: python benchmarks/check_regression.py \
           --baseline /tmp/baseline.json --fresh BENCH_kernels.json
"""
from __future__ import annotations

import argparse
import json
import re
import sys

IDENT_RE = re.compile(
    r"(weights?_identical|identical_to_batched|identical_to_local"
    r"|identical_to_reference|certified_sound|warm_identical)=(True|False)")


def _rows(path: str) -> dict[str, dict]:
    with open(path) as f:
        rec = json.load(f)
    return {r["name"]: r for r in rec.get("rows", [])}


def _ident_flags(derived: str) -> list[tuple[str, bool]]:
    return [(m.group(1), m.group(2) == "True")
            for m in IDENT_RE.finditer(derived or "")]


def check(baseline: dict[str, dict], fresh: dict[str, dict],
          factor: float) -> list[str]:
    failures = []
    for name in sorted(set(baseline) & set(fresh)):
        b, f = baseline[name], fresh[name]
        for key, ok in _ident_flags(f.get("derived", "")):
            if not ok:
                failures.append(
                    f"{name}: correctness flag {key} is False "
                    f"(derived={f['derived']!r})")
        bu, fu = b.get("us_per_call"), f.get("us_per_call")
        if bu and fu and fu > factor * bu:
            failures.append(
                f"{name}: {fu:.1f}us vs baseline {bu:.1f}us "
                f"(> {factor}x regression)")
    return failures


def _derived_value(derived: str, key: str) -> float | None:
    m = re.search(rf"\b{re.escape(key)}=([0-9.eE+-]+)", derived or "")
    try:
        return float(m.group(1)) if m else None
    except ValueError:
        return None


def check_serving(rows: dict[str, dict], min_rps: float, max_p99_us: float,
                  min_speedup: float) -> list[str]:
    """Absolute SLO gates for a fresh ``BENCH_serving.json`` (optional
    check — ``--serving FRESH``).

    Unlike the baseline-relative throughput gate, serving is gated on
    *absolute* floors/ceilings: the stream runs on a simulated arrival
    clock, so its numbers are dominated by the configured load plus the
    measured solve walls, and an absolute bound catches the real
    failure modes (compile-per-request, a stalled batcher, warm path
    slower than cold) without flaking on runner-to-runner speed spread.
    The ``warm_identical`` correctness flag is enforced by the shared
    flag scan in :func:`check`; here it is enforced even WITHOUT a
    baseline (a fresh-only run must not skip it)."""
    failures = []
    for name in ("serving_throughput", "serving_latency",
                 "serving_warm_vs_cold"):
        if name not in rows:
            failures.append(f"serving: required row {name!r} is missing")
    for name, r in sorted(rows.items()):
        for key, ok in _ident_flags(r.get("derived", "")):
            if not ok:
                failures.append(
                    f"{name}: correctness flag {key} is False "
                    f"(derived={r['derived']!r})")
    r = rows.get("serving_throughput")
    if r is not None:
        rps = _derived_value(r.get("derived", ""), "throughput_rps")
        if rps is None:
            failures.append("serving_throughput: no throughput_rps in "
                            f"derived ({r.get('derived')!r})")
        elif rps < min_rps:
            failures.append(
                f"serving_throughput: {rps:.1f} rps under the "
                f"{min_rps:.1f} rps floor")
    r = rows.get("serving_latency")
    if r is not None:
        p99 = _derived_value(r.get("derived", ""), "p99_us")
        if p99 is None:
            failures.append("serving_latency: no p99_us in derived "
                            f"({r.get('derived')!r})")
        elif p99 > max_p99_us:
            failures.append(
                f"serving_latency: p99 {p99:.0f}us over the "
                f"{max_p99_us:.0f}us ceiling")
    r = rows.get("serving_warm_vs_cold")
    if r is not None:
        speedup = _derived_value(r.get("derived", ""), "speedup")
        if speedup is None:
            failures.append("serving_warm_vs_cold: no speedup in derived "
                            f"({r.get('derived')!r})")
        elif speedup < min_speedup:
            failures.append(
                f"serving_warm_vs_cold: warm-start speedup {speedup:.2f}x "
                f"under the {min_speedup:.2f}x floor (warm rematching must "
                f"beat cold solve on perturbed repeats)")
    return failures


def check_solver(rows: dict[str, dict], max_residual: float) -> list[str]:
    """Absolute gate for a fresh ``BENCH_solver.json`` (``--solver FRESH``).

    The solver experiments (``results/fill_experiments.py``) are gated on
    the claims they exist to demonstrate, not on timing:

    1. every ``awpm``-arm row (and every ``reference``-arm row present)
       must have converged with true relative residual <= ``max_residual``
       — AWPM static pivoting + iterative refinement must work on EVERY
       case;
    2. at least one case must show the contrast — its ``none`` arm failed
       (diverged/stalled refinement) while its ``awpm`` arm converged.
       That contrast IS the reproduced result; a sweep where unpivoted LU
       quietly succeeds everywhere no longer demonstrates anything.
    """
    failures = []
    by_case: dict[str, dict[str, dict]] = {}
    for name, r in rows.items():
        m = re.match(r"solver_(.+)_(awpm|reference|none|tpp)$", name)
        if m:
            by_case.setdefault(m.group(1), {})[m.group(2)] = r
    if not by_case:
        return ["solver: no solver_<case>_<arm> rows found"]
    for case in sorted(by_case):
        for arm in ("awpm", "reference"):
            r = by_case[case].get(arm)
            if r is None:
                if arm == "awpm":
                    failures.append(f"solver {case}: awpm row is missing")
                continue  # reference is optional (scipy-less runners)
            derived = r.get("derived", "")
            res = _derived_value(derived, "residual")
            if "converged=True" not in derived:
                failures.append(
                    f"solver {case} [{arm}]: did not converge "
                    f"(derived={derived!r})")
            elif res is None or res > max_residual:
                failures.append(
                    f"solver {case} [{arm}]: residual "
                    f"{res if res is not None else 'missing'} over the "
                    f"{max_residual:g} ceiling")
    contrast = [
        case for case, arms in sorted(by_case.items())
        if "converged=False" in arms.get("none", {}).get("derived", "")
        and "converged=True" in arms.get("awpm", {}).get("derived", "")]
    if not contrast:
        failures.append(
            "solver: no case shows the none-fails/awpm-converges contrast "
            "— the experiment no longer demonstrates that matching-based "
            "static pivoting replaces numerical pivoting")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline")
    ap.add_argument("--fresh")
    ap.add_argument("--factor", type=float, default=2.5)
    ap.add_argument("--serving", metavar="FRESH",
                    help="gate a fresh BENCH_serving.json on absolute "
                         "SLOs (throughput floor, p99 ceiling, warm-start "
                         "speedup floor + bit-identity flag)")
    ap.add_argument("--serving-min-rps", type=float, default=20.0)
    ap.add_argument("--serving-max-p99-us", type=float, default=250_000.0)
    ap.add_argument("--serving-min-speedup", type=float, default=1.05)
    ap.add_argument("--solver", metavar="FRESH",
                    help="gate a fresh BENCH_solver.json on absolute "
                         "claims: every awpm row converged under the "
                         "residual ceiling, and >= 1 case where the "
                         "unpivoted arm failed while awpm converged")
    ap.add_argument("--solver-max-residual", type=float, default=1e-10)
    args = ap.parse_args()
    if bool(args.baseline) != bool(args.fresh):
        ap.error("--baseline and --fresh go together")
    if not args.baseline and not args.serving and not args.solver:
        ap.error("nothing to do: pass --baseline/--fresh, --serving, "
                 "and/or --solver")
    failures = []
    n = 0
    if args.baseline:
        baseline, fresh = _rows(args.baseline), _rows(args.fresh)
        only_b = sorted(set(baseline) - set(fresh))
        only_f = sorted(set(fresh) - set(baseline))
        if only_b:
            print(f"# rows only in baseline (ignored): {only_b}")
        if only_f:
            print(f"# new rows (not gated yet): {only_f}")
        n = len(set(baseline) & set(fresh))
        failures += check(baseline, fresh, args.factor)
    if args.serving:
        failures += check_serving(
            _rows(args.serving), args.serving_min_rps,
            args.serving_max_p99_us, args.serving_min_speedup)
    if args.solver:
        failures += check_solver(_rows(args.solver),
                                 args.solver_max_residual)
    for msg in failures:
        print(f"FAIL {msg}")
    if failures:
        sys.exit(1)
    parts = []
    if args.baseline:
        parts.append(f"{n} shared rows within {args.factor}x, all "
                     f"correctness flags True")
    if args.serving:
        parts.append(f"serving SLOs met (>= {args.serving_min_rps:.0f} rps, "
                     f"p99 <= {args.serving_max_p99_us:.0f}us, warm >= "
                     f"{args.serving_min_speedup:.2f}x)")
    if args.solver:
        parts.append(f"solver: awpm converged <= "
                     f"{args.solver_max_residual:g} on every case, "
                     f"unpivoted-fails contrast present")
    print(f"# regression gate OK: {'; '.join(parts)}")


if __name__ == "__main__":
    main()
