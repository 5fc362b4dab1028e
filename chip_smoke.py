#!/usr/bin/env python3
"""Smoke run of the matching engine on a TPU, through its public entry points.

  python chip_smoke.py             # one chip: phases (a)-(d)
  python chip_smoke.py --chips 4   # four chips: phase (a), then (e) only

Phases, each printing its own line:

  (a) device    platform, device kind and count; anything but a TPU exits
                non-zero before any other phase runs.
  (b) oracle    the five ``graph.SUITE_KINDS`` at n=512 through ``solve()``:
                mates, weight and AWAC iterations bit-identical to the same
                call with its inputs on the host CPU device, and the AWAC
                phase bit-identical to the numpy oracle
                (``core.ref.awac_parallel_rule``) from the engine's own MCM
                matching. The end-to-end oracle ``core.ref.awpm_reference``
                is printed, not held: its sequential greedy and Kuhn MCM
                start AWAC from another perfect matching than the engine's
                proposal rounds and layered BFS, so it ends elsewhere.
  (c) main      one instance at the size of ``configs/awpm_paper.py``
                (n = 4,194,304, average degree 16) through
                ``solve(..., SolveOptions(backend="auto"))``, checked by the
                host verifier ``runtime.resilient.verify_result``.
  (d) serving   ``MatchingService`` on the wall clock: 64 drifting requests
                at n = 1024-4096 over the batched engine and the plan cache.
  (e) grid      (``--chips 4`` only) ``solve()`` on a 2x2 grid of the four
                chips against the same instance on one chip, bit-identical.

Every instance is generated from ``--seed`` by ``core/graph.py``. Every
solve prints its ``MatchResult.execution`` and must not have run in the
Pallas interpreter. The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import time
import traceback

REPO = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

PAPER_N = 4_194_304  # configs/awpm_paper.py::config()
PAPER_DEGREE = 16
GRID_N = 1 << 20


class PhaseError(RuntimeError):
    """A phase's result is wrong."""


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


def check_execution(phase: str, label: str, result) -> None:
    say(phase, f"{label} execution={result.execution}")
    require(result.execution is not None
            and result.execution.ran_interpreted is False,
            f"{label}: ran in the Pallas interpreter ({result.execution})")


def block(result) -> None:
    import jax

    jax.block_until_ready((result.mate_row, result.mate_col, result.weight,
                           result.awac_iters))


_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_total = 0.0


def _on_duration(event: str, duration: float, **_) -> None:
    global _compile_total
    if event in _COMPILE_EVENTS:
        _compile_total += duration


@functools.cache
def _listen_for_compiles() -> None:
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_seconds() -> float:
    """Seconds this process has spent lowering and compiling (or fetching
    from the compilation cache) since the first call, as jax's monitoring
    events report them. Tracing is left out: its events nest."""
    _listen_for_compiles()
    return _compile_total


def same_result(a, b) -> list[str]:
    """Fields in which two single-instance MatchResults differ bitwise."""
    import numpy as np

    diff = []
    for name in ("mate_row", "mate_col", "awac_iters", "weight"):
        x, y = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        if x.dtype != y.dtype or x.shape != y.shape \
                or x.tobytes() != y.tobytes():
            diff.append(f"{name} ({x.ravel()[:4]} vs {y.ravel()[:4]})")
    return diff


# --------------------------------------------------------------------------
# (a) device
# --------------------------------------------------------------------------


def phase_device(chips: int, platform: str = "tpu") -> dict:
    """The attached devices as JAX reports them. Fails unless the first is
    a ``platform`` device and at least ``chips`` are attached."""
    import jax

    devices = jax.devices()
    d = devices[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
    say("a device", f"platform={info['platform']} kind={info['kind']} "
                    f"count={info['count']}")
    require(d.platform == platform,
            f"no {platform} device: jax.devices()[0] is {d.platform}")
    require(len(devices) >= chips,
            f"{chips} chips requested, {len(devices)} attached")
    return info


# --------------------------------------------------------------------------
# (b) oracle
# --------------------------------------------------------------------------


def phase_oracle(n: int = 512, degree: float = 8.0, seed: int = 0,
                 kinds=None) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import MatchingProblem, graph, ref, single, solve

    cpu = jax.devices("cpu")[0]
    for kind in kinds or graph.SUITE_KINDS:
        g = graph.generate(n, avg_degree=degree, kind=kind, seed=seed)
        problem = MatchingProblem.from_graph(g)
        res = solve(problem)
        block(res)
        check_execution("b oracle", kind, res)
        with jax.default_device(cpu):
            on_cpu = MatchingProblem(
                *(jax.device_put(x, cpu) for x in (g.row, g.col, g.val)),
                n=g.n)
            res_cpu = solve(on_cpu)
            block(res_cpu)
        require(res_cpu.mate_row.devices() == {cpu},
                f"{kind}: the host run left the CPU device")
        diff = same_result(res, res_cpu)
        require(not diff, f"{kind}: differs from the CPU run in "
                          f"{', '.join(diff)}")

        # numpy oracle of the AWAC phase, from the engine's own MCM matching
        row, col, val = (jnp.asarray(x) for x in (g.row, g.col, g.val))
        st = single.greedy_maximal(row, col, val, g.n)
        st = single.mcm(row, col, val, g.n, st.mate_row, st.mate_col)
        mr1 = np.asarray(st.mate_row)[:n].astype(np.int64)
        mc1 = np.asarray(st.mate_col)[:n].astype(np.int64)
        dense = g.to_dense().astype(np.float32)
        struct = g.structure_dense()
        require(ref.is_perfect(mr1, n), f"{kind}: MCM is not perfect")
        mr_ref, mc_ref, rounds = ref.awac_parallel_rule(dense, struct, mr1,
                                                        mc1)
        mates = np.asarray(res.mate_row)[:n]
        require(np.array_equal(mates, mr_ref),
                f"{kind}: mates differ from the numpy oracle in "
                f"{int((mates != mr_ref).sum())} columns")
        # the engine also counts the final round, which finds no cycle
        require(int(res.awac_iters) == rounds + 1,
                f"{kind}: {int(res.awac_iters)} AWAC rounds, oracle "
                f"{rounds} + 1")
        w_ref = ref.ordered_sum(dense[np.arange(n), mc_ref])
        require(np.float32(res.weight).tobytes() == w_ref.tobytes(),
                f"{kind}: weight {float(res.weight)!r} != oracle "
                f"{float(w_ref)!r}")
        # The end-to-end oracle runs a sequential greedy and Kuhn's MCM, so
        # its AWAC starts from another perfect matching: reported, not held.
        mr_full, _, _ = ref.awpm_reference(dense, struct)
        w_full = ref.matching_weight(dense, mr_full)
        same = np.array_equal(mates, mr_full)
        say("b oracle", f"{kind}: awpm_reference end to end (not held): "
                        f"mates {'equal' if same else 'differ'}, weight "
                        f"{w_full!r} against {float(res.weight)!r}")
        say("b oracle", f"{kind} n={n} nnz={g.nnz} ok: mates, weight "
                        f"{float(res.weight)!r} and {int(res.awac_iters)} "
                        f"AWAC rounds equal to the CPU run and to the AWAC-"
                        f"phase oracle awac_parallel_rule")


# --------------------------------------------------------------------------
# (c) main path at the paper's size
# --------------------------------------------------------------------------


def phase_main(n: int = PAPER_N, degree: float = PAPER_DEGREE,
               seed: int = 0) -> None:
    import jax

    from repro.core import MatchingProblem, SolveOptions, graph, solve
    from repro.core.preflight import preflight
    from repro.runtime.resilient import verify_result

    t0 = time.perf_counter()
    g = graph.generate(n, avg_degree=degree, kind="uniform", seed=seed)
    host = MatchingProblem.from_graph(g)
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    arrays = jax.device_put((g.row, g.col, g.val))
    jax.block_until_ready(arrays)
    problem = MatchingProblem(*arrays, n=g.n)
    t_put = time.perf_counter() - t0
    say("c main", f"n={n} nnz={g.nnz} cap={g.capacity}: generate "
                  f"{t_gen:.3f} s, host-to-device {t_put:.3f} s")

    t0 = time.perf_counter()
    preflight(problem)
    t_pre = time.perf_counter() - t0
    options = SolveOptions(backend="auto")
    compile_s = compile_seconds()
    t0 = time.perf_counter()
    res = solve(problem, options)
    block(res)
    t_call = time.perf_counter() - t0
    t_compile = compile_seconds() - compile_s
    check_execution("c main", "solve", res)
    require(res.execution.backend == "xla",
            f"auto resolved to {res.execution.backend!r}, not 'xla'")
    stats = res.mate_row.devices().pop().memory_stats() or {}
    say("c main", f"solve call {t_call:.3f} s: compile {t_compile:.3f} s "
                  f"(lowering + XLA compile or cache fetch), host preflight "
                  f"{t_pre:.3f} s (timed alone), device solve and the rest "
                  f"{t_call - t_compile - t_pre:.3f} s; device "
                  f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    t0 = time.perf_counter()
    fails = verify_result(host, res, options)
    t_verify = time.perf_counter() - t0
    require(not fails, f"verify_result: {'; '.join(fails)}")
    require(bool(res.perfect), "the matching is not perfect")
    say("c main", f"ok: perfect, weight {float(res.weight)!r}, "
                  f"{int(res.awac_iters)} AWAC rounds, verified on the host "
                  f"in {t_verify:.3f} s")


# --------------------------------------------------------------------------
# (d) serving on the wall clock
# --------------------------------------------------------------------------


def phase_serving(sizes=(1024, 2048, 4096), requests: int = 64,
                  users: int = 8, degree: float = 8.0,
                  seed: int = 0) -> None:
    import numpy as np

    from repro.core import graph
    from repro.serving import MatchingService, ServiceConfig
    from repro.serving.loadgen import perturbed

    rng = np.random.default_rng(seed)
    bases = [graph.generate(sizes[u % len(sizes)], avg_degree=degree,
                            seed=seed * 1009 + u) for u in range(users)]
    service = MatchingService(ServiceConfig(max_class_n=max(sizes)))
    t0 = time.perf_counter()
    for i in range(requests):
        u = i % users
        service.submit(f"user-{u}", perturbed(bases[u], rng, 0.02, 0.1))
    service.drain()
    wall = time.perf_counter() - t0
    responses = service.responses()
    require(len(responses) == requests,
            f"{len(responses)} responses for {requests} requests")
    bad = [r.request_id for r in responses
           if not (r.ok and r.result is not None and r.result.perfect)]
    require(not bad, f"requests {bad[:8]} not ok and perfect")
    for r in responses:
        require(r.result.execution.ran_interpreted is False,
                f"request {r.request_id} ran in the Pallas interpreter")
    lat = np.array([r.latency_s for r in responses])
    stats = service.stats()
    say("d serving", f"execution={responses[0].result.execution}")
    say("d serving", f"ok: {requests} requests, n in {sorted(set(sizes))}, "
                     f"all perfect; {stats['served_warm']} warm / "
                     f"{stats['served_cold']} cold; {stats['flushes']} "
                     f"batches; plan cache {stats['plan_cache']['misses']} "
                     f"misses / {stats['plan_cache']['hits']} hits; wall "
                     f"{wall:.3f} s with compiles; latency p50 "
                     f"{np.percentile(lat, 50):.4f} s p99 "
                     f"{np.percentile(lat, 99):.4f} s")


# --------------------------------------------------------------------------
# (e) the 2x2 grid on four chips
# --------------------------------------------------------------------------


def phase_grid(n: int = GRID_N, degree: float = PAPER_DEGREE,
               seed: int = 0) -> None:
    import jax

    from repro.core import MatchingProblem, SolveOptions, graph, solve
    from repro.core.dist import make_mesh

    g = graph.generate(n, avg_degree=degree, kind="uniform", seed=seed)
    problem = MatchingProblem.from_graph(g)
    mesh = make_mesh((2, 2))
    c0, t0 = compile_seconds(), time.perf_counter()
    res_grid = solve(problem, SolveOptions(grid=mesh))
    block(res_grid)
    t_grid, c_grid = time.perf_counter() - t0, compile_seconds() - c0
    check_execution("e grid", "2x2", res_grid)
    for d in mesh.devices.ravel():
        stats = d.memory_stats() or {}
        say("e grid", f"device {d.id}: bytes_in_use="
                      f"{stats.get('bytes_in_use')} peak_bytes_in_use="
                      f"{stats.get('peak_bytes_in_use')}")
    c0, t0 = compile_seconds(), time.perf_counter()
    res_one = solve(problem)
    block(res_one)
    t_one, c_one = time.perf_counter() - t0, compile_seconds() - c0
    check_execution("e grid", "one chip", res_one)
    diff = same_result(res_grid, res_one)
    require(not diff, f"2x2 grid differs from one chip in {', '.join(diff)}")
    require(bool(res_grid.perfect), "the matching is not perfect")
    say("e grid", f"ok: n={n} nnz={g.nnz}, 2x2 grid bit-identical to one "
                  f"chip (weight {float(res_grid.weight)!r}, "
                  f"{int(res_grid.awac_iters)} AWAC rounds); solve calls: "
                  f"grid {t_grid:.3f} s (compile {c_grid:.3f} s), one chip "
                  f"{t_one:.3f} s (compile {c_one:.3f} s)")


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2 grid phase on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError:
        print("chip_smoke: the repro package is not next to this script",
              file=sys.stderr)
        return 2
    say("cache", f"compilation cache at {enable_compile_cache()}")
    try:
        device = phase_device(args.chips)
    except PhaseError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    if args.chips == 4:
        phases = [("e grid", lambda: phase_grid(seed=args.seed))]
    else:
        phases = [("b oracle", lambda: phase_oracle(seed=args.seed)),
                  ("c main", lambda: phase_main(seed=args.seed)),
                  ("d serving", lambda: phase_serving(seed=args.seed))]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception as e:  # noqa: BLE001 — report every phase
            traceback.print_exc()
            say(name, f"FAILED after {time.perf_counter() - t0:.3f} s: "
                      f"{type(e).__name__}: {e}")
            failed.append(name)
        else:
            say(name, f"passed in {time.perf_counter() - t0:.3f} s")
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
