"""Paper Fig 6.1/6.3: runtime vs problem size + strong-scaling model.

Reported here:
  (a) measured single-device AWPM runtime across matrix sizes (the paper's
      "sequential AWPM" baseline),
  (b) the analytic strong-scaling model of §5.3 evaluated with v5e constants
      (alpha-beta costs of the 4 AWAC steps on a sqrt(p) x sqrt(p) grid),
      reproducing the shape of Fig 6.3,
  (c) measured AWAC per-round cost decomposition (requests, join, select).
  (d) measured distributed-BATCHED throughput (DESIGN.md §5): one
      planned ``Matcher`` dispatch for B instances on a p-device 2D grid,
      p in {1, 2, 4, 8} x B in {1, 8, 32}. Grids the attached devices can
      hold run in-process; on the CPU, larger grids run in a child with
      fake host devices (see ``run_dist_batched``).
"""
import os
import pathlib
import re
import subprocess
import sys

import numpy as np

from benchmarks._util import row, time_call
from repro.core import MatchingProblem, graph, solve

REPO = pathlib.Path(__file__).resolve().parents[1]

# p -> 2D mesh shape (both orientations are CI-tested; the bench uses one)
DIST_MESHES = {1: (1, 1), 2: (1, 2), 4: (2, 2), 8: (2, 4)}

def dist_rows(p: int, n: int, deg: float, emit) -> None:
    """Distributed-batched rows for one p-device grid of ``jax.devices()``;
    ``emit(name, us, derived)`` receives each row."""
    import time

    import jax

    from repro.core import SolveOptions, plan
    from repro.runtime.straggler import StragglerMonitor

    pr, pc = DIST_MESHES[p]
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:p]).reshape(pr, pc), ("data", "model"))
    # 1x1 grid routes Steps A+B+C through core.batch's fused sweep directly
    backend = "xla" if p == 1 else "fused"
    for b in (1, 8, 32):
        gs = [graph.generate(n, avg_degree=deg, kind="uniform", seed=s)
              for s in range(b)]
        problem = MatchingProblem.stack(gs)
        # plan once: capacity + bucket planning, engine build; each timed
        # call is partition + one shard_map dispatch (serving shape)
        matcher = plan(problem, SolveOptions(grid=mesh, backend=backend))
        res = matcher(problem)  # compile + warmup
        jax.block_until_ready(res)
        # straggler monitor over the serving loop: each dispatch is one
        # "step" on serving rank 0 (the cross-rank z-score path is idle on
        # one host — slow_steps() is the single-rank alarm)
        mon = StragglerMonitor(alpha=0.2, threshold=2.0, warmup=5)
        reps = 6
        t0 = time.perf_counter()
        for step in range(reps):
            ts = time.perf_counter()
            out = matcher(problem)
            jax.block_until_ready(out)
            mon.record(step, time.perf_counter() - ts, rank=0)
        dt = (time.perf_counter() - t0) / reps
        slow = mon.slow_ranks() or mon.slow_steps()
        resL = solve(problem)
        ident = bool(np.array_equal(np.array(resL.mate_row),
                                    np.array(res.mate_row)))
        # timed=serving: each rep includes host partition + device_put +
        # dispatch (the real serving shape), not just the compiled engine
        emit(f"awpm_dist_batched_p{p}_B{b}", dt / b * 1e6,
             f"matchings_per_s={b / dt:.1f};mesh={pr}x{pc};"
             f"backend={backend};timed=serving;identical_to_local={ident};"
             f"straggler_flagged={'|'.join(map(str, slow)) or 'none'}")


DIST_CHILD = r"""
from benchmarks.bench_scaling import dist_rows

dist_rows({p}, {n}, {deg},
          lambda name, us, d: print(f"ROW,{{name}},{{us:.1f}},{{d}}",
                                    flush=True))
"""


def run_dist_batched(n: int = 24, deg: float = 6.0):
    """Distributed-batched matchings/sec rows, p in DIST_MESHES. A grid the
    attached devices can hold runs in this process. Only on the CPU does a
    larger grid run in a child with fake host devices (the device count
    must be set before jax initializes); on an accelerator it is skipped,
    because this process holds the chip and a child must never need it."""
    import jax

    for p in DIST_MESHES:
        if p <= jax.device_count():
            dist_rows(p, n, deg, row)
            continue
        if jax.default_backend() != "cpu":
            print(f"# skip p={p}: only {jax.device_count()} "
                  f"{jax.default_backend()} device(s) attached", flush=True)
            continue
        env = dict(os.environ)
        # strip any inherited device-count token entirely — XLA aborts on
        # unknown flags, so the stale token can't just be renamed
        inherited = re.sub(r"--xla_force_host_platform_device_count=\S+", "",
                           env.get("XLA_FLAGS", ""))
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={p} {inherited}").strip()
        env["JAX_PLATFORMS"] = "cpu"  # fake host devices, never a chip
        env["PYTHONPATH"] = (f"{REPO}:{REPO / 'src'}:"
                             f"{env.get('PYTHONPATH', '')}")
        script = DIST_CHILD.format(p=p, n=n, deg=deg)
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True, env=env,
                              timeout=1800)
        if proc.returncode != 0:
            raise RuntimeError(
                f"dist bench subprocess p={p} failed\n{proc.stdout}\n"
                f"{proc.stderr}")
        for line in proc.stdout.splitlines():
            if line.startswith("ROW,"):
                _, name, us, derived = line.split(",", 3)
                row(name, float(us), derived)


ALPHA = 1e-6  # s per message (ICI latency)
BETA = 1.0 / 50e9  # s per byte per link
GAMMA = 1.0 / 197e12  # s per flop


def analytic_awac_round(n, m, p):
    """T = F + alpha*S + beta*W for one AWAC round on p devices (§5.3)."""
    flops = (m / p) * 16 + 8 * n  # relabel+join (edge work) + replicated O(n)
    words_a2a = 12 * m / p  # two-stage exchange, 12B/entry
    words_gather = 16 * n / np.sqrt(p)  # step C/D winner gathers
    msgs = 2 * np.sqrt(p) + 2
    return flops * GAMMA + ALPHA * msgs + BETA * (words_a2a + words_gather)


def run(sizes=(256, 512, 1024, 2048), deg=8.0):
    for n in sizes:
        g = graph.generate(n, avg_degree=deg, kind="uniform", seed=1)
        problem = MatchingProblem.from_graph(g)
        dt, res = time_call(lambda: solve(problem), iters=2, warmup=1)
        row(f"awpm_single_n{n}", dt * 1e6,
            f"m={g.nnz};iters={int(res.awac_iters)};"
            f"w={float(res.weight):.1f}")
    # strong-scaling model (paper Fig 6.3 analogue) for the match_4m cell
    n, m = 4_194_304, 67_108_864
    t1 = analytic_awac_round(n, m, 1)
    for p in (1, 4, 16, 64, 256, 512):
        tp = analytic_awac_round(n, m, p)
        row(f"awac_model_p{p}", tp * 1e6, f"speedup={t1 / tp:.1f}x")
    # measured distributed-batched throughput on simulated device grids
    run_dist_batched()
    return True


if __name__ == "__main__":
    run()
