#!/usr/bin/env python3
"""Benchmark harness: one cell of ``BENCHMARK.json`` on the attached chips.

  python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration and a traffic mix; both are data files found
by name (``chipbench/configs/<config>.json``, ``chipbench/traffic/<traffic>.json``),
and every metric is read by a file of its own (``chipbench/metrics/<metric>.py``,
whose ``read(ctx)`` returns a number or None). Adding a configuration, a
traffic mix or a metric takes new files and entries only.

A run:

1. fails (exit 2, no result) unless JAX's first device is a TPU and at least
   the cell's number of chips is attached;
2. set-up: pins the C allocator's thresholds (``pin_allocator``), builds
   the instance and its chain of drifting values on the host (from the
   traffic's ``instance_seed``, or from ``--seed``), and warms the cell's
   route with the traffic's warm-up solves, with JAX's compilation cache
   at ``<checkout>/.jax_cache``;
3. ``--trace 0``: walks the chain for ``--seconds`` as the traffic says
   (``Workload``) and reports the cell's end-to-end metrics; ``--trace 1``:
   traces the traffic's ``traced_solves`` solves instead and reports its
   per-layer metrics, the device's busy and window seconds, and a
   breakdown;
4. checks what the timed solves produced: every matching is a perfect
   matching on the instance's edges and identical to the plain reference
   (``chipbench/reference.py``) in mates, AWAC rounds and weight bits.

The last line of standard output is one JSON object; the compared numbers,
each beside its limit, are the last lines of standard error and the last
key of that object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
#: Fixed, inside the checkout: the path is part of the cache's key.
CACHE_DIR = ROOT / ".jax_cache"

if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from chipbench import gen, reference, trace  # noqa: E402


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, a missing file, ...)."""


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# data files, found by name
# --------------------------------------------------------------------------


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no {path}")
    return json.loads(path.read_text())


def find_cell(benchmark: dict, name: str) -> dict:
    for cell in benchmark["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def load_data(bench_dir: pathlib.Path, kind: str, name: str) -> dict:
    """``<bench_dir>/<kind>/<name>.json``: a configuration or a traffic mix."""
    path = bench_dir / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def load_reader(bench_dir: pathlib.Path, metric: str):
    """``<bench_dir>/metrics/<metric>.py``, whose ``read(ctx)`` gives the
    metric or None."""
    path = bench_dir / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise BenchError(f"no reader {path} for metric {metric!r}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cell_metrics(benchmark: dict, section: str, cell_name: str) -> list[dict]:
    """The entries of ``section`` that this cell reports."""
    return [m for m in benchmark[section]
            if cell_name in m.get("workloads", [cell_name])]


def peaks_for(bench_dir: pathlib.Path, device_kind: str) -> dict:
    table = json.loads((bench_dir / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json; add them with their source")
    return table["devices"][device_kind]


# --------------------------------------------------------------------------
# devices and JAX
# --------------------------------------------------------------------------


#: glibc's ``mallopt`` parameters, and the values every run pins them to.
M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3
MMAP_THRESHOLD = 32 << 20  # glibc's own ceiling for its dynamic threshold
TRIM_THRESHOLD = 1 << 30


def pin_allocator() -> None:
    """Fix the C allocator's thresholds, so that every run starts in the
    state a long-lived process settles in. Left alone, glibc raises its
    mmap threshold the first time any thread frees a large block: a run in
    which that happens early (as when the process compiles) serves a
    solve's 4-16 MB arrays from a heap it keeps, one in which it does not
    maps and faults them in anew on every call, and its solves are about
    1.7 % slower."""
    import ctypes

    libc = ctypes.CDLL("libc.so.6")
    for param, value in ((M_MMAP_THRESHOLD, MMAP_THRESHOLD),
                         (M_TRIM_THRESHOLD, TRIM_THRESHOLD)):
        if libc.mallopt(param, value) != 1:
            raise BenchError(f"mallopt({param}, {value}) failed")


def start_jax(chips: int):
    """Import JAX with the checkout's compilation cache and return it with
    the attached devices' description. Raises unless the first device is a
    TPU and at least ``chips`` are attached. The TPU runtime's logs go
    inside the checkout unless the environment sends them elsewhere."""
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".tpu_logs"))
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if info["platform"] != "tpu":
        raise BenchError(f"no TPU attached: JAX's first device is "
                         f"{info['platform']} ({info['kind']})")
    if info["count"] < chips:
        raise BenchError(f"the cell asks for {chips} chips, {info['count']} "
                         f"attached")
    return jax, info


class CompileCounter:
    """Seconds and events of lowering and compiling (or fetching from the
    compilation cache), as JAX's monitoring events report them."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration
            self.events += 1


def solve_options(config: dict):
    """``SolveOptions`` from the configuration's ``solve_options``; a
    ``grid`` entry [rows, cols] becomes a mesh of the attached chips."""
    from repro.core.api import SolveOptions

    opts = dict(config["solve_options"])
    if "grid" in opts:
        from repro.core.dist import make_mesh

        opts["grid"] = make_mesh(tuple(opts["grid"]))
    return SolveOptions(**opts)


def route_solve(problem, options, warm_start=None):
    """The program's entry that the window drives."""
    from repro.core import api

    return api.solve(problem, options, warm_start=warm_start)


# --------------------------------------------------------------------------
# traffic
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Solve:
    index: int  # position in the run's history of solves, warm-up included
    link: int  # index into the drift chain
    warm_from: int | None  # history index of the solve this one started from
    seconds: float  # host arrays in -> mates in host memory
    latency_s: float  # arrival -> mates in host memory
    mate_row: np.ndarray | None
    awac_rounds: int | None
    weight: np.ndarray | None  # float32 scalar, as returned
    error: str | None = None


def walk_pingpong(k: int, rng: np.random.Generator):
    """0 -> last -> 0 and again, from link 1."""
    period = max(2 * (k - 1), 1)
    i = 1
    while True:
        p = i % period
        yield p if p < k else period - p
        i += 1


def walk_shuffle(k: int, rng: np.random.Generator):
    """Passes over links 1 .. k-1, each pass in an order of its own drawn
    from the seed, never one link twice in a row: every seed solves the
    same links, in another order."""
    if k < 3:
        raise BenchError("a shuffled walk needs a chain of 3 links or more")
    last = None
    while True:
        order = rng.permutation(np.arange(1, k))
        if order[0] == last:
            order[[0, -1]] = order[[-1, 0]]
        yield from (int(x) for x in order)
        last = int(order[-1])


#: A traffic's ``walk``: the order in which the window visits the chain's
#: links. Link 0 is the warm-up's.
WALKS = {"pingpong": walk_pingpong, "shuffle": walk_shuffle}


class Workload:
    """The set-up product: the instance, its drift chain, the route, and
    the traffic's walk, start and arrivals. Traffic keys:

    - ``instance_seed``: the seed of the pattern and the chain, fixed for
      every run; null takes them from ``--seed``, which always sets the
      walk's order and the arrivals;
    - ``chain_length``, ``weight_jitter``: the drift chain (``gen.drift_chain``);
    - ``walk``: a key of ``WALKS``;
    - ``start``: ``cold`` (greedy and MCM from scratch) or ``warm`` (each
      solve seeded with the previous solve's matching);
    - ``loop``: ``closed`` (one caller, the next request when the last is
      answered) or ``open`` (Poisson arrivals at ``rate_per_s``, answered
      in order, latency counted from arrival);
    - ``traced_solves``: how many solves the ``--trace 1`` run traces.
    """

    def __init__(self, config: dict, traffic: dict, seed: int):
        if traffic["walk"] not in WALKS:
            raise BenchError(f"traffic walk {traffic['walk']!r}: the walks "
                             f"are {sorted(WALKS)}")
        if traffic["start"] not in ("cold", "warm"):
            raise BenchError(f"traffic start {traffic['start']!r}: cold or "
                             f"warm")
        if traffic["loop"] not in ("closed", "open"):
            raise BenchError(f"traffic loop {traffic['loop']!r}: closed or "
                             f"open")
        n = int(config["n"])
        capacity = -(-int(n * config["avg_degree"]) // 8) * 8
        fixed = traffic.get("instance_seed")
        instance = seed if fixed is None else int(fixed)
        self.graph = gen.generate(n, config["avg_degree"], config["kind"],
                                  seed=instance, capacity=capacity)
        self.chain = gen.drift_chain(
            self.graph, int(traffic["chain_length"]),
            np.random.default_rng((instance, 1)),
            float(traffic["weight_jitter"]))
        self.options = solve_options(config)
        self.n = n
        self.seed = seed
        self.traffic = traffic
        self.history: list[Solve] = []
        self._last = None  # (history index, MatchResult) to warm-start from

    def links(self):
        return WALKS[self.traffic["walk"]](len(self.chain),
                                           np.random.default_rng((self.seed, 3)))

    def arrivals(self):
        """Offsets from the window's start (open loop)."""
        rng = np.random.default_rng((self.seed, 4))
        mean_gap = 1.0 / float(self.traffic["rate_per_s"])
        t = 0.0
        while True:
            t += rng.exponential(mean_gap)
            yield t

    def warm_up(self) -> list[Solve]:
        """One cold solve of link 0, and for a warm start one warm solve of
        it from that: every program the window runs, compiled."""
        solves = [self.step(0, cold=True)]
        if self.traffic["start"] == "warm":
            solves.append(self.step(0))
        return solves

    def step(self, link: int, spans: bool = False, arrival: float | None = None,
             cold: bool = False) -> Solve:
        """One matching: host arrays in, mates in host memory."""
        import jax
        from repro.core.api import MatchingProblem

        ann = jax.profiler.TraceAnnotation if spans \
            else (lambda _: contextlib.nullcontext())
        warm = None if cold or self.traffic["start"] == "cold" else self._last
        index = len(self.history)
        t0 = time.perf_counter()
        try:
            with ann(trace.SPANS[0]):
                problem = MatchingProblem(row=self.graph.row,
                                          col=self.graph.col,
                                          val=self.chain[link], n=self.n)
            with ann(trace.SPANS[1]):
                result = route_solve(problem, self.options,
                                     warm_start=None if warm is None
                                     else warm[1])
            with ann(trace.SPANS[2]):
                mate_row = np.asarray(result.mate_row)
                rounds = int(np.asarray(result.awac_iters))
                weight = np.asarray(result.weight)
            error = None
        except Exception as e:  # a solve that fails counts as failed
            mate_row = rounds = weight = None
            error = f"{type(e).__name__}: {e}"
        end = time.perf_counter()
        s = Solve(index, link, None if warm is None else warm[0], end - t0,
                  end - (t0 if arrival is None else arrival), mate_row, rounds,
                  weight, error)
        self.history.append(s)
        self._last = None if error else (index, result)
        return s


def run_window(workload: Workload, seconds: float):
    """Solve link after link until ``seconds`` have passed; the window ends
    with the last solve. An open loop waits for each arrival and stops at
    the first one past the window."""
    solves = []
    links = workload.links()
    arrivals = workload.arrivals() if workload.traffic["loop"] == "open" \
        else None
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        due = None
        if arrivals is not None:
            offset = next(arrivals)
            if offset >= seconds:
                break
            due = t0 + offset
            time.sleep(max(0.0, due - time.perf_counter()))
        solves.append(workload.step(next(links), arrival=due))
    return solves, time.perf_counter() - t0


def run_traced(jax, workload: Workload, count: int, trace_dir: pathlib.Path):
    """``count`` solves under the profiler, each in the benchmark's spans."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    links = workload.links()
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        solves = [workload.step(next(links), spans=True) for _ in range(count)]
    paths = sorted(trace_dir.glob("**/*.xplane.pb"))
    if not paths:
        raise BenchError(f"the profiler wrote no trace under {trace_dir}")
    return solves, trace.load(paths[-1])


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------


def invalid_matching(g: gen.Graph, mate_row: np.ndarray) -> bool:
    """True unless every column is matched to a distinct row by an edge."""
    n = g.n
    rows = np.asarray(mate_row)[:n].astype(np.int64)
    if rows.shape != (n,) or (rows < 0).any() or (rows >= n).any():
        return True
    if np.unique(rows).size != n:
        return True
    key = g.row[:g.nnz].astype(np.int64) * (n + 1) + g.col[:g.nnz]
    q = rows * (n + 1) + np.arange(n)
    pos = np.minimum(np.searchsorted(key, q), key.size - 1)
    return bool((key[pos] != q).any())


def references(workload: Workload, solves: list[Solve]) -> dict:
    """The reference's matching for each of ``solves``, by history index.
    A warm solve's reference starts from the reference's own matching of
    the solve it was seeded from, so a warm chain is replayed from its
    cold start."""
    g = workload.graph
    need = {s.index for s in solves}
    for s in reversed(workload.history):
        if s.index in need and s.warm_from is not None:
            need.add(s.warm_from)
    refs = {}
    for s in workload.history:
        if s.index in need:
            seed = None if s.warm_from is None else refs[s.warm_from]
            refs[s.index] = reference.solve(
                g.row, g.col, workload.chain[s.link], g.n,
                warm=None if seed is None else (seed.mate_row, seed.mate_col))
    return refs


def check(workload: Workload, solves: list[Solve]) -> dict:
    """The compared numbers, each {value, limit}. All limits are 0: the
    engine's answer is exact, and its reference gives the same bits. Every
    matching the run timed is compared."""
    g = workload.graph
    done = [s for s in solves if s.error is None]
    invalid = sum(invalid_matching(g, s.mate_row) for s in done)
    refs = references(workload, done)
    rows_off = rounds_off = weight_off = 0
    for s in done:
        ref = refs[s.index]
        rows_off += int((s.mate_row[:g.n] != ref.mate_row[:g.n]).sum())
        rounds_off += abs(s.awac_rounds - ref.awac_rounds)
        weight_off += int(np.asarray(s.weight, np.float32).tobytes()
                          != np.asarray(ref.weight, np.float32).tobytes())
    say(f"compared {len(done)} matchings with the reference")
    return {
        "solves_failed": {"value": len(solves) - len(done), "limit": 0},
        "not_perfect": {"value": int(invalid), "limit": 0},
        "mates_off": {"value": rows_off, "limit": 0},
        "rounds_off": {"value": rounds_off, "limit": 0},
        "weight_bits_off": {"value": weight_off, "limit": 0},
    }


def checks_pass(checks: dict, attempted: int) -> bool:
    return attempted > 0 and all(c["value"] <= c["limit"]
                                 for c in checks.values())


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    config: dict
    traffic: dict
    setup_s: float
    solves: list  # [Solve] of the window, or of the traced solves
    window_s: float | None  # measured window (trace 0)
    trace: trace.Trace | None  # (trace 1)
    n: int
    nnz: int
    peaks: dict
    modules: dict  # phase -> [module names], metrics/modules.json


def read_metrics(bench_dir, entries, ctx) -> dict:
    out = {}
    for entry in entries:
        value = load_reader(bench_dir, entry["name"]).read(ctx)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def breakdown(tr: trace.Trace) -> dict:
    lo, hi = tr.window
    dev = tr.devices[min(tr.devices)]
    ops = sorted(trace.self_times(dev, lo, hi).items(), key=lambda kv: -kv[1])
    gaps = trace.idle_gaps(dev, tr.spans, lo, hi)
    return {"device_ops": [[k, v / 1e9] for k, v in ops[:10]],
            "idle_gaps": [[k, v / 1e9] for k, v in gaps[:10]]}


def host_usage(a, b) -> str:
    """What the process cost the host between two ``getrusage`` readings:
    CPU seconds, page faults and context switches, and the load average."""
    return (f"host: user {b.ru_utime - a.ru_utime:.3f} s, sys "
            f"{b.ru_stime - a.ru_stime:.3f} s, minor faults "
            f"{b.ru_minflt - a.ru_minflt}, major faults "
            f"{b.ru_majflt - a.ru_majflt}, voluntary switches "
            f"{b.ru_nvcsw - a.ru_nvcsw}, involuntary "
            f"{b.ru_nivcsw - a.ru_nivcsw}; load "
            + " ".join(f"{x:.2f}" for x in os.getloadavg()))


def memory_peak(jax) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if args.seed < 0:
        p.error("--seed must be >= 0")
    return args


def run(args, root: pathlib.Path = ROOT, bench_dir: pathlib.Path = BENCH_DIR,
        start_jax=start_jax) -> tuple[dict, dict]:
    """One run; returns (result line, checks)."""
    benchmark = load_benchmark(root)
    cell = find_cell(benchmark, args.workload)
    config = load_data(bench_dir, "configs", cell["config"])
    traffic = load_data(bench_dir, "traffic", cell["traffic"])
    if not (root / "src" / "repro").is_dir():
        raise BenchError(f"the program is not in this checkout "
                         f"({root / 'src' / 'repro'} is missing)")
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    jax, device = start_jax(int(cell["chips"]))
    peaks = peaks_for(bench_dir, device["kind"])
    compiles = CompileCounter(jax)

    t = time.perf_counter()
    workload = Workload(config, traffic, args.seed)
    say(f"set-up: import and devices {t - T_START:.3f} s, instance and "
        f"chain {time.perf_counter() - t:.3f} s")
    warm = workload.warm_up()
    for s in warm:
        if s.error:
            raise BenchError(f"the warm-up solve failed: {s.error}")
    setup_s = time.perf_counter() - T_START
    say(f"set-up: {setup_s:.3f} s, of it compile {compiles.seconds:.3f} s "
        f"in {compiles.events} events; warm-up solves "
        + " ".join(f"{s.seconds:.3f}" for s in warm) + " s")

    before = compiles.events
    usage = resource.getrusage(resource.RUSAGE_SELF)
    trace_dir = None
    try:
        if args.trace:
            trace_dir = pathlib.Path(tempfile.mkdtemp(prefix="chipbench-"))
            solves, tr = run_traced(jax, workload,
                                    int(traffic["traced_solves"]), trace_dir)
            window_s = None
        else:
            solves, window_s = run_window(workload, args.seconds)
            tr = None
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    say("solves (link, seconds, AWAC rounds): " + " ".join(
        f"({s.link},{s.seconds:.4f},{s.awac_rounds})" for s in solves))
    say(host_usage(usage, resource.getrusage(resource.RUSAGE_SELF)))
    if compiles.events > before:
        say(f"WARNING: {compiles.events - before} compile events inside the "
            f"window")
    mem = memory_peak(jax)

    ctx = Context(config, traffic, setup_s, solves, window_s, tr,
                  workload.n, workload.graph.nnz, peaks,
                  json.loads((bench_dir / "metrics" / "modules.json")
                             .read_text()))
    section = "per_layer" if args.trace else "end_to_end"
    metrics = read_metrics(bench_dir, cell_metrics(benchmark, section,
                                                   cell["name"]), ctx)
    device = dict(device, memory_peak_bytes=mem)
    line = {}
    if tr is not None:
        if not tr.devices:
            raise BenchError("the trace holds no TPU device")
        lo, hi = tr.window
        busy = [trace.busy_ns(d, lo, hi) for d in tr.devices.values()]
        device["busy_s"] = sum(busy) / len(busy) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        line["breakdown"] = breakdown(tr)

    t = time.perf_counter()
    checks = check(workload, solves)
    say(f"reference comparison {time.perf_counter() - t:.3f} s")
    failed = checks["solves_failed"]["value"] + checks["not_perfect"]["value"]
    line = {"correct": checks_pass(checks, len(solves)),
            "attempted": len(solves), "failed": failed, "metrics": metrics, "device": device, **line,
            "checks": checks}
    return line, checks


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        pin_allocator()
        line, checks = run(args)
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(line), flush=True)
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
