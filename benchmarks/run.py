"""Benchmark harness — one module per paper table/figure (+ beyond-paper).
Prints ``name,us_per_call,derived`` CSV rows and persists each suite's rows
to ``BENCH_<suite>.json`` at the repo root (suite name, wall-clock, row list,
environment metadata) so the perf trajectory is tracked across PRs. See
EXPERIMENTS.md for the mapping to the paper's tables."""
import argparse
import datetime
import json
import pathlib
import sys
import time
import traceback

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def _persist(suite: str, rows, wall_clock_s: float, ok: bool, full: bool):
    import jax

    rec = {
        "suite": suite,
        "ok": ok,
        "wall_clock_s": round(wall_clock_s, 3),
        "rows": rows,
        "metadata": {
            "date": datetime.datetime.now(datetime.timezone.utc).isoformat(),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "full": full,
        },
    }
    out = REPO_ROOT / f"BENCH_{suite}.json"
    out.write_text(json.dumps(rec, indent=1))
    print(f"# wrote {out.name} ({len(rows)} rows, {wall_clock_s:.1f}s)",
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names (approx_ratio, scaling, "
                         "pivot, moe_router, kernels, serving)")
    ap.add_argument("--full", action="store_true",
                    help="larger problem sizes (slower)")
    ap.add_argument("--no-persist", action="store_true",
                    help="skip writing BENCH_*.json files")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_approx_ratio, bench_kernels, bench_moe_router, bench_pivot,
        bench_scaling, bench_serving,
    )
    from benchmarks._util import drain_rows

    benches = {
        "approx_ratio": lambda: bench_approx_ratio.run(
            n_matrices=100 if args.full else 50, n=120 if args.full else 96),
        "scaling": bench_scaling.run,
        "pivot": bench_pivot.run,
        "moe_router": bench_moe_router.run,
        "kernels": bench_kernels.run,
        "serving": lambda: bench_serving.run(quick=not args.full),
    }
    selected = (args.only.split(",") if args.only else list(benches))
    unknown = [s for s in selected if s not in benches]
    if unknown:
        ap.error(f"unknown bench name(s) {unknown}; "
                 f"choose from {sorted(benches)}")
    print("name,us_per_call,derived")
    failures = 0
    for name in selected:
        print(f"# --- {name} ---", flush=True)
        drain_rows()  # discard anything a previous suite left behind
        t0 = time.perf_counter()
        ok = True
        try:
            benches[name]()
        except Exception:  # noqa: BLE001
            failures += 1
            ok = False
            traceback.print_exc()
        if not args.no_persist:
            _persist(name, drain_rows(), time.perf_counter() - t0, ok,
                     args.full)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
