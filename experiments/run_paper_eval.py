"""Paper-eval runner (DESIGN.md §8): sweep the Matrix Market fixtures +
synthetic-suite matrices across backends (reference / xla / pallas) and
device grids (1x1 / 2x2) through the ``solve()``/``Matcher`` facade;
certify every result with LP-dual potentials; fail loudly on unsound
bounds, backend disagreement, or imperfect matchings.

Outputs: ``results/paper_eval.md`` (per-matrix table) and
``BENCH_paper_eval.json`` at the repo root (gated in CI by
``benchmarks/check_regression.py``).

  PYTHONPATH=src python experiments/run_paper_eval.py [--quick]
      [--backends reference,xla,pallas] [--grids 1x1,2x2]
      [--suite-count 10] [--suite-n 96] [--transform log2_scaled_nonneg]
      [--no-persist] [--download [--instances Freescale1,rajat31]
      [--cache-dir DIR]]

``--download`` is the only network path in the repo (opt-in, sha256-pinned
cache via ``repro.data.suitesparse``); without it the sweep runs entirely
on checked-in fixtures.

``--quick`` is the CI docs-job smoke: fixtures + 3 small synthetic
matrices, reference/xla backends, the 1x1 grid — every correctness check
still runs, only the sweep is smaller.
"""
import argparse
import pathlib
import sys
import time

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT / "src"))

from repro.experiments import paper_eval  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def _parse_grids(text: str):
    grids = []
    for tok in text.split(","):
        tok = tok.strip()
        if not tok:
            continue
        try:
            pr, pc = (int(t) for t in tok.split("x"))
        except ValueError:
            raise SystemExit(f"bad grid {tok!r}: expected PRxPC, e.g. 2x2")
        grids.append((pr, pc))
    return grids


def main() -> None:
    ap = argparse.ArgumentParser(
        description="AWPM quality evaluation in the paper's metric")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: fixtures + 3 small synthetic matrices, "
                         "reference/xla, 1x1 grid")
    ap.add_argument("--backends", default=None,
                    help="comma list from reference,xla,pallas "
                         "(default: those the platform compiles, all three "
                         "on the CPU; --quick: reference,xla)")
    ap.add_argument("--grids", default=None,
                    help="comma list of PRxPC grids (default: 1x1,2x2; "
                         "--quick: 1x1). On the CPU, grids beyond the "
                         "attached device count run in a fake-device "
                         "subprocess; on an accelerator they are skipped.")
    ap.add_argument("--suite-count", type=int, default=None,
                    help="number of synthetic suite matrices (default 10)")
    ap.add_argument("--suite-n", type=int, default=None,
                    help="synthetic matrix size (default 96)")
    ap.add_argument("--transform", default=None,
                    help="re-measure the synthetic suite in this weight "
                         "metric (e.g. log2_scaled_nonneg); default: its "
                         "native rowcol normalization")
    ap.add_argument("--oracle-max-n", type=int, default=256,
                    help="run the exact scipy oracle up to this n")
    ap.add_argument("--no-persist", action="store_true",
                    help="skip writing results/ + BENCH_paper_eval.json")
    ap.add_argument("--download", action="store_true",
                    help="OPT-IN network: fetch SuiteSparse instances "
                         "(sha256-pinned cache) and sweep them too. CI "
                         "never passes this — fixtures need no network.")
    ap.add_argument("--instances", default=None,
                    help="with --download: comma list of registry names or "
                         "Group/name specs (default: the paper registry)")
    ap.add_argument("--cache-dir", default=None,
                    help="SuiteSparse cache dir (default: "
                         "$REPRO_SUITESPARSE_CACHE or "
                         "~/.cache/repro-suitesparse)")
    args = ap.parse_args()

    enable_compile_cache()
    spec = dict(paper_eval.QUICK_SPEC if args.quick
                else paper_eval.DEFAULT_SPEC)
    if args.suite_count is not None:
        spec["synthetic_count"] = args.suite_count
    if args.suite_n is not None:
        spec["synthetic_n"] = args.suite_n
    if args.transform is not None:
        spec["synthetic_transform"] = args.transform
    if args.instances and not args.download:
        raise SystemExit("--instances needs --download (no implicit network)")
    if args.download:
        from repro.data import suitesparse

        names = ([t.strip() for t in args.instances.split(",") if t.strip()]
                 if args.instances else None)
        fetched = suitesparse.fetch_paper_instances(names,
                                                    cache=args.cache_dir)
        spec["extra_mtx"] = sorted(str(p) for p in fetched.values())
        print(f"# suitesparse: {len(fetched)} instance(s) cached under "
              f"{suitesparse.cache_dir(args.cache_dir)}")
    backends = (args.backends.split(",") if args.backends
                else (["reference", "xla"] if args.quick
                      else list(paper_eval.default_backends())))
    grids = _parse_grids(args.grids) if args.grids \
        else ([(1, 1)] if args.quick else list(paper_eval.GRIDS))

    t0 = time.perf_counter()
    records = paper_eval.run_eval(spec, backends=backends, grids=grids,
                                  oracle_max_n=args.oracle_max_n)
    wall = time.perf_counter() - t0
    print(paper_eval.to_markdown(records))
    n_tight = sum(r.tight for r in records)
    bounds = [r.ratio_bound for r in records if r.ratio_bound is not None]
    print(f"# {len(records)} rows in {wall:.1f}s: {n_tight} certified "
          f"optimal, min certified ratio bound "
          f"{min(bounds):.4f}" if bounds else "# no ratio bounds", flush=True)
    if not args.no_persist:
        table, bench = paper_eval.write_outputs(records, wall,
                                                quick=args.quick)
        print(f"# wrote {table.relative_to(REPO_ROOT)} and {bench.name} "
              f"({len(records)} rows)")


if __name__ == "__main__":
    main()
