"""Quickstart: approximate-weight perfect matching through the unified API.

Generates a synthetic matrix (planted perfect matching, paper-style
normalization), builds a ``MatchingProblem``, runs the full AWPM pipeline
(greedy maximal -> maximum cardinality -> augmenting 4-cycles) with one
``solve()`` call, and compares against the exact optimum. The same call
solves a whole batch — ``MatchingProblem.stack`` + the same options.

  PYTHONPATH=src python examples/quickstart.py [--n 400] [--kind antigreedy]
"""
import argparse

import numpy as np

from repro.core import MatchingProblem, SolveOptions, graph, ref, solve


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=400)
    ap.add_argument("--degree", type=float, default=6.0)
    ap.add_argument("--kind", default="antigreedy",
                    choices=list(graph.SUITE_KINDS))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "reference", "xla"])
    args = ap.parse_args()

    g = graph.generate(args.n, avg_degree=args.degree, kind=args.kind,
                       seed=args.seed)
    print(f"matrix: n={g.n} nnz={g.nnz} kind={args.kind}")

    problem = MatchingProblem.from_graph(g)
    res = solve(problem, SolveOptions(backend=args.backend))
    w = float(res.weight)
    print(f"AWPM solve():            perfect={bool(res.perfect)}, "
          f"{int(res.awac_iters)} AWAC rounds, weight {w:.3f}")

    # batched: the same facade solves many instances in one dispatch
    batch_problem = MatchingProblem.stack(
        [graph.generate(args.n, avg_degree=args.degree, kind=args.kind,
                        seed=args.seed + i) for i in range(4)])
    res_b = solve(batch_problem, SolveOptions(backend=args.backend))
    print(f"batched solve() (B=4):   perfect={np.array(res_b.perfect)}, "
          f"weights {np.round(np.array(res_b.weight), 2)}")
    assert np.array_equal(np.array(res_b.mate_row[0]), np.array(res.mate_row))

    dense = g.to_dense().astype(np.float32)
    struct = g.structure_dense()
    _, opt = ref.exact_mwpm(dense, struct)
    mr = np.array(res.mate_row[: g.n])
    ref.check_matching(struct, mr)
    print(f"optimum (Hungarian):     {opt:.3f}")
    print(f"approximation ratio:     {w / opt:.4f} "
          f"(paper: typically >= 0.99, always >= 2/3)")
    assert w / opt >= 2 / 3


if __name__ == "__main__":
    main()
