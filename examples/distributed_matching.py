"""Distributed AWPM on a 2D device grid through the unified API: the ONLY
change vs a local solve is ``SolveOptions(grid=mesh)``, and ``plan()`` gives
a compile-once/run-many ``Matcher`` for serving many batches.

The grid is the largest power-of-two rectangle of the attached devices
(2x2 on a four-chip host). With ``JAX_PLATFORMS=cpu`` the example rehearses
a 4x4 grid on 16 fake host devices; everything runs in this one process.

  PYTHONPATH=src python examples/distributed_matching.py [--n 256]
  JAX_PLATFORMS=cpu PYTHONPATH=src python examples/distributed_matching.py
"""
import argparse
import os

if os.environ.get("JAX_PLATFORMS") == "cpu":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=16")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import MatchingProblem, SolveOptions, graph, plan, ref, solve  # noqa: E402
from repro.core.dist import make_mesh  # noqa: E402


def grid_shape(count: int) -> tuple[int, int]:
    """Largest power-of-two (pr, pc) grid with pr <= pc and pr * pc <= count."""
    k = count.bit_length() - 1
    return 2 ** (k // 2), 2 ** (k - k // 2)


def main(n=256, degree=8.0, seed=0, batch=4):
    pr, pc = grid_shape(len(jax.devices()))
    mesh = make_mesh((pr, pc))
    g = graph.generate(n, avg_degree=degree, kind="uniform", seed=seed)
    print(f"matrix n={g.n} nnz={g.nnz} on a {pr}x{pc} process grid "
          f"({len(jax.devices())} {jax.default_backend()} devices)")

    # one-shot: identical call shape to the local path, plus grid=
    problem = MatchingProblem.from_graph(g)
    res = solve(problem, SolveOptions(grid=mesh))
    print(f"distributed solve(): weight={float(res.weight):.3f}, "
          f"AWAC rounds={int(res.awac_iters)}, perfect={bool(res.perfect)}")

    res_local = solve(problem)
    same = np.array_equal(np.array(res.mate_row[:n]),
                          np.array(res_local.mate_row[:n]))
    print(f"bit-identical to the local solve: {same}")
    assert same, "distributed result diverged from the local solve"

    dense = g.to_dense().astype(np.float32)
    _, opt = ref.exact_mwpm(dense, g.structure_dense())
    print(f"approximation ratio: {float(res.weight) / opt:.4f}")

    # serving: plan once (capacity planning + engine build), run many
    gs = [graph.generate(n, avg_degree=degree, kind="uniform", seed=seed + i)
          for i in range(batch)]
    batch_problem = MatchingProblem.stack(gs)
    matcher = plan(batch_problem, SolveOptions(grid=mesh))
    print(f"planned: {matcher}")
    res_b = matcher(batch_problem)
    res_b2 = matcher(MatchingProblem.stack(list(reversed(gs))))
    same_b = np.array_equal(np.array(res_b.mate_row[0]),
                            np.array(res_b2.mate_row[-1]))
    print(f"matcher: B={batch} weights="
          f"{np.round(np.array(res_b.weight), 2)}, reuse across calls "
          f"bit-identical: {same_b}")
    assert same_b, "Matcher reuse diverged across calls"


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--degree", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args()
    main(n=args.n, degree=args.degree, seed=args.seed, batch=args.batch)
