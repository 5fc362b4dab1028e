"""Spans and counters of ``repro.core.telemetry``.

Contract under test: each ``solve()`` leaves one record, with a
``repro.solve`` root and its layers' spans nested inside it on one
``solve_id``; the counters the engines carry in their while_loops equal a
plain numpy count of the same work, and are identical for the same solve
on every route that can run it; the records stay bounded, a traced solve
records nothing, and the named scopes reach the lowered programs. The
spans also land in a profiler trace on the device's clock, inside the
benchmark's own spans, without changing what the benchmark reads.
"""
import pathlib
import tempfile
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _subproc import run_with_devices

from chipbench import reference
from chipbench import run as bench_run
from chipbench import trace as bench_trace
from repro.core import api, graph, single, telemetry
from repro.core.constants import MIN_GAIN
from repro.sparse.csr import row_ptr_from_sorted, window_depth


def _last_record():
    (rec,) = telemetry.recent(1)
    return rec


def numpy_counts(row, col, val, n):
    """Greedy rounds (the last matches nothing), pairs greedy matched, MCM
    phases and BFS layers, and 4-cycles augmented, counted by replaying
    the numpy reference's loops (``chipbench/reference.py``) on one padded
    instance."""
    real = row < n
    row = row[real].astype(np.int64)
    col = col[real].astype(np.int64)
    val = val[real]
    mate_row = np.full(n + 1, n, np.int64)
    mate_col = np.full(n + 1, n, np.int64)
    eidx = np.arange(row.size)
    rounds = 0
    while True:  # reference.greedy, one proposal round at a time
        rounds += 1
        e = eidx[(mate_col[row] == n) & (mate_row[col] == n)]
        pv, pe = reference._best_per_segment(val[e], e, col[e], n)
        j = np.flatnonzero(pe >= 0)
        _, rj = reference._best_per_segment(pv[j], j, row[pe[j]], n)
        ok = np.flatnonzero(rj >= 0)
        if ok.size == 0:
            break
        mate_col[ok] = rj[ok]
        mate_row[rj[ok]] = ok
    matched = int((mate_row[:n] < n).sum())
    phases = layers = 0
    while (mate_row[:n] == n).any():  # reference.mcm, one phase at a time
        parent_col, visited, found, phase_layers = reference._bfs(
            row, col, val, n, mate_row, mate_col)
        mate_row, mate_col = reference._trace_and_flip(
            parent_col, visited, found, phase_layers, mate_row, mate_col, n)
        phases += 1
        layers += phase_layers
        if not found:
            break
    key = row * (n + 1) + col
    u, v = reference._matched_weights(key, val, n, mate_row, mate_col,
                                      np.float32)
    augmented, go = 0, True
    while go:  # reference.solve's AWAC rounds, survivors summed
        survivors = reference.awac_round(row, col, val, key, n, mate_row,
                                         mate_col, u, v,
                                         np.float32(reference.MIN_GAIN))
        augmented += survivors
        go = survivors > 0
    return {"greedy_rounds": rounds, "greedy_matched": matched,
            "mcm_phases": phases, "mcm_bfs_layers": layers,
            "awac_augmented": augmented}


@pytest.mark.parametrize("kind,n,degree,seed,min_phases", [
    ("uniform", 256, 6.0, 1, 1),
    # proposal rounds that leave many rows free: MCM needs several phases
    ("antigreedy", 128, 4.0, 7, 3),
])
def test_counters_equal_the_numpy_count(kind, n, degree, seed, min_phases):
    g = graph.generate(n, avg_degree=degree, kind=kind, seed=seed)
    result = api.solve(api.MatchingProblem.from_graph(g))
    counters = _last_record()["counters"]
    want = numpy_counts(g.row, g.col, g.val, n)
    assert want["mcm_phases"] >= min_phases, want
    for name in ("greedy_rounds", "greedy_matched", "mcm_bfs_layers",
                 "awac_augmented"):
        assert counters[name] == want[name], (name, counters, want)
    # every BFS layer of the single route picks parents by the sorted scan
    assert counters["mcm_sorted_layers"] == counters["mcm_bfs_layers"]
    iters = int(result.awac_iters)
    assert (counters["awac_augmented"] > 0) == (iters > 1), (counters, iters)
    # numpy row, col, val: each engine call copies them anew — greedy, MCM
    # and the AWAC loop all three, row_ptr_from_sorted the rows
    assert counters["h2d_bytes"] == 10 * g.row.nbytes


def test_device_arrays_are_not_counted_as_copies():
    g = graph.generate(64, avg_degree=4.0, kind="uniform", seed=3)
    api.solve(api.MatchingProblem(row=jnp.asarray(g.row),
                                  col=jnp.asarray(g.col),
                                  val=jnp.asarray(g.val), n=g.n))
    assert _last_record()["counters"]["h2d_bytes"] == 0


ROUTES_SCRIPT = r"""
import json
import numpy as np
import jax
from repro.core import api, dist, graph, telemetry

n = 128
g = graph.generate(n, avg_degree=5.0, kind="antigreedy", seed=11)
p = api.MatchingProblem.from_graph(g)
grid = api.SolveOptions(grid=dist.make_mesh((2, 2)))

def counters(result):
    rec = telemetry.recent(1)[0]["counters"]
    return {k: rec[k] for k in ("greedy_rounds", "greedy_matched",
                                "mcm_bfs_layers", "awac_augmented")}, \
        np.asarray(result.mate_row).reshape(-1).tolist(), \
        rec["mcm_sorted_layers"], \
        [rec[k] for k in telemetry.GRID_COUNTERS]

out = {}
cold = api.solve(p)
if START == "cold":
    out["single"] = counters(cold)
    out["batched B=1"] = counters(api.solve(api.MatchingProblem.stack([g])))
    out["grid 2x2"] = counters(api.solve(p, grid))
    # the grid program's lowered text holds every named scope
    drv = dist._DistBatchedAWPM(grid.grid, n, degrade_infeasible=True)
    part, brow, bcol, bval, ws = drv.partition(g.row[None], g.col[None],
                                               g.val[None])
    engine = dist._make_awpm_dist_batched(
        grid.grid, n, 1, part.cap, dist.safe_a2a_caps(part.cap, 2, 2),
        window_steps=ws, degrade_infeasible=True)
    with jax.enable_x64(True):
        text = engine.lower(brow, bcol, bval).as_text(debug_info=True)
    scopes = [s for s in ("greedy_round", "mcm_bfs_layer", "mcm_trace_flip",
                          "awac_winners", "awac_select_augment", "a2a_exchange")
              if s in text]
else:
    # the same pattern with drifted values, warm-started from the cold
    # matching with 16 columns unmatched: a repair, an MCM top-up and AWAC,
    # no greedy
    rng = np.random.default_rng(5)
    drifted = (g.val * (1 + 0.05 * rng.random(g.val.shape))).astype(
        np.float32)
    p2 = api.MatchingProblem(row=g.row, col=g.col, val=drifted, n=n)
    seed = (np.asarray(cold.mate_row).copy(), np.asarray(cold.mate_col))
    seed[0][:16] = n
    out["single"] = counters(api.solve(p2, warm_start=seed))
    out["grid 2x2"] = counters(api.solve(p2, grid, warm_start=seed))
    scopes = None
print(json.dumps([out, scopes]))
"""


@pytest.mark.parametrize("start", ["cold", "warm"])
def test_counters_identical_on_every_route(start):
    """Cold: the single route, the B = 1 batched route and the 2x2 grid;
    warm: the warm route and the grid. A warm solve runs no greedy. The
    cold single route and the cold grid pick BFS parents with the
    sorted-segment scan, so their ``mcm_sorted_layers`` equal their
    ``mcm_bfs_layers``; the batched route and every warm solve, whose MCM
    runs on the batched engine, report None. Only the grid keeps the
    exchange's counters (``a2a_*``); every other route reports None
    there."""
    import json

    out, scopes = json.loads(run_with_devices(
        f"START = {start!r}\n" + ROUTES_SCRIPT, 4)
        .strip().splitlines()[-1])
    first_counters, first_mates, first_sorted, first_a2a = out.pop("single")
    assert first_a2a == [None, None, None]
    for route, (counters, mates, sorted_layers, a2a) in out.items():
        assert counters == first_counters, (route, counters, first_counters)
        assert mates == first_mates, route
        scanned = start == "cold" and route == "grid 2x2"
        assert sorted_layers == (counters["mcm_bfs_layers"] if scanned
                                 else None), (route, sorted_layers)
        if route == "grid 2x2":
            slots, filled, nbytes = a2a
            assert 0 < filled <= slots and nbytes == 13 * slots, a2a
        else:
            assert a2a == [None, None, None], route
    assert first_counters["mcm_bfs_layers"] > 0
    assert first_sorted == (first_counters["mcm_bfs_layers"]
                            if start == "cold" else None)
    if start == "cold":
        assert scopes == ["greedy_round", "mcm_bfs_layer", "mcm_trace_flip",
                          "awac_winners", "awac_select_augment", "a2a_exchange"]
    else:
        assert first_counters["greedy_rounds"] == 0
        assert first_counters["greedy_matched"] == 0


EXCHANGE_SCRIPT = r"""
import json, sys
import numpy as np
from repro.core import api, dist, graph, telemetry

kind, packed = sys.argv[1], sys.argv[2] == "packed"
n = 128
g = graph.generate(n, avg_degree=6.0, kind=kind, seed=9)
opts = api.SolveOptions(grid=dist.make_mesh((2, 2)), packed=packed)
api.solve(api.MatchingProblem.from_graph(g), opts)
rec = telemetry.recent(1)[0]["counters"]
# the partition and the caps `_DistBatchedAWPM` plans for this instance
drv = dist._DistBatchedAWPM(opts.grid, n)
part = drv.partition(g.row[None], g.col[None], g.val[None])[0]
print(json.dumps({
    "counters": {k: rec[k] for k in telemetry.GRID_COUNTERS},
    "row": part.row.tolist(), "col": part.col.tolist(),
    "caps": dist.safe_a2a_caps(part.cap, 2, 2)}))
"""


def numpy_exchange(row, col, n, caps, states):
    """Slots and filled slots of the two-stage bucketed exchange, counted
    by routing each round's candidates in numpy: ``row``/``col`` the
    [pr, pc, 1, cap] blocks, ``states`` the (mate_row, mate_col) each AWAC
    round starts from."""
    pr, pc = row.shape[:2]
    br, bc = -(-n // pr), -(-n // pc)
    cap1, cap2 = caps
    slots = filled = 0
    for mate_row, mate_col in states:
        slots += pr * pc * (pc * cap1 + pr * cap2)
        received = [[[] for _ in range(pc)] for _ in range(pr)]
        for a in range(pr):
            for b in range(pc):
                r, c = row[a, b, 0], col[a, b, 0]
                i2, j2 = mate_row[c], mate_col[r]
                ok = (r < n) & (i2 < n) & (j2 < n)
                for d in range(pc):  # stage 1: to the grid column of j2
                    sent = i2[ok & (j2 // bc == d)][:cap1]
                    filled += sent.size
                    received[a][d].append(sent)
        for a in range(pr):
            for b in range(pc):  # stage 2: to the grid row of i2
                i2 = np.concatenate(received[a][b])
                filled += sum(min(int((i2 // br == d).sum()), cap2)
                              for d in range(pr))
    return slots, filled


@pytest.mark.parametrize("kind,packed", [("uniform", "unpacked"),
                                         ("banded", "packed")])
def test_exchange_counters_equal_the_numpy_count(kind, packed):
    """``a2a_slots`` and ``a2a_filled`` on the 2x2 grid equal a numpy
    routing of every AWAC round's candidates through the same partition and
    the same bucket capacities; ``a2a_bytes`` is the slots times 12 bytes
    of payload, plus 1 of validity on the unpacked path."""
    import json

    out = json.loads(run_with_devices(
        f"import sys; sys.argv[1:] = [{kind!r}, {packed!r}]\n"
        + EXCHANGE_SCRIPT, 4).strip().splitlines()[-1])
    n = 128
    g = graph.generate(n, avg_degree=6.0, kind=kind, seed=9)
    real = g.row < n
    row, col = g.row[real].astype(np.int64), g.col[real].astype(np.int64)
    val = g.val[real]
    mate_row, mate_col = reference.greedy(row, col, val, n)
    mate_row, mate_col = reference.mcm(row, col, val, n, mate_row, mate_col)
    key = row * (n + 1) + col
    u, v = reference._matched_weights(key, val, n, mate_row, mate_col,
                                      np.float32)
    states, go = [], True
    while go:  # reference.solve's AWAC rounds, each one's starting state
        states.append((mate_row.copy(), mate_col.copy()))
        go = reference.awac_round(row, col, val, key, n, mate_row, mate_col,
                                  u, v, np.float32(reference.MIN_GAIN)) > 0
    slots, filled = numpy_exchange(np.array(out["row"]), np.array(out["col"]),
                                   n, out["caps"], states)
    got = out["counters"]
    assert (got["a2a_slots"], got["a2a_filled"]) == (slots, filled), \
        (got, slots, filled)
    assert 0 < got["a2a_filled"] <= got["a2a_slots"]
    assert got["a2a_bytes"] == (12 if packed == "packed" else 13) * slots


def test_span_tree():
    ids_before = {r["solve_id"] for r in telemetry.recent(telemetry.CAPACITY)}
    for seed in (1, 2):
        g = graph.generate(64, avg_degree=4.0, kind="uniform", seed=seed)
        api.solve(api.MatchingProblem.from_graph(g))
    records = telemetry.recent(2)
    assert len({r["solve_id"] for r in records}) == 2
    assert not ids_before & {r["solve_id"] for r in records}
    for rec in records:
        spans = rec["spans"]
        roots = [s for s in spans if s["parent"] is None]
        assert [s["name"] for s in roots] == ["repro.solve"]
        names = {s["name"] for s in spans}
        assert {"repro.preflight", "repro.greedy", "repro.mcm",
                "repro.window_depth", "repro.row_ptr", "repro.awac",
                "repro.result", "repro.finish"} <= names
        for s in spans:
            assert s["solve_id"] == rec["solve_id"]
            assert s["name"].startswith("repro.")
            assert s["name"] not in bench_trace.SPANS
            assert s["start_ns"] <= s["end_ns"]
            if s["parent"] is not None:
                parent = spans[s["parent"]]
                assert parent["start_ns"] <= s["start_ns"]
                assert s["end_ns"] <= parent["end_ns"]


def test_buffer_keeps_the_last_records():
    for _ in range(telemetry.CAPACITY + 6):
        with telemetry.record():
            pass
    records = telemetry.recent(telemetry.CAPACITY + 6)
    assert len(records) == telemetry.CAPACITY
    ids = [r["solve_id"] for r in records]
    assert ids == list(range(ids[0], ids[0] + telemetry.CAPACITY))
    assert telemetry.recent(0) == []


def test_solve_under_jit_records_nothing():
    g = graph.generate(64, avg_degree=4.0, kind="uniform", seed=4)
    problem = api.MatchingProblem.from_graph(g)
    with telemetry.record():  # a known last record
        pass
    before = telemetry.recent(1)
    with jax.checking_leaks():
        out = jax.jit(lambda p: api.solve(p))(problem)
    assert bool(out.perfect)
    assert telemetry.recent(1) == before


def _edge_shapes(n, cap):
    edges = (jax.ShapeDtypeStruct((cap,), jnp.int32),
             jax.ShapeDtypeStruct((cap,), jnp.int32),
             jax.ShapeDtypeStruct((cap,), jnp.float32))
    st = single.MatchState(*(jax.ShapeDtypeStruct((n + 1,), dt) for dt in
                             (jnp.int32, jnp.int32, jnp.float32,
                              jnp.float32)))
    return edges, st


def test_named_scopes_in_the_lowered_phases():
    n, cap = 64, 512
    (row, col, val), st = _edge_shapes(n, cap)
    row_ptr = jax.ShapeDtypeStruct((n + 2,), jnp.int32)
    lowered = {
        "greedy_maximal": single._greedy_counted.lower(row, col, val, n),
        "mcm": single._mcm_counted.lower(row, col, val, row_ptr, n,
                                         st.mate_row, st.mate_col, 6),
    }
    with jax.enable_x64(True):
        lowered["_awac_loop"] = single._awac_counted.lower(
            row, col, val, row_ptr, n, st, 1000, MIN_GAIN, "xla",
            window_depth(n))
    scopes = {"greedy_maximal": ["greedy_round"],
              "mcm": ["mcm_bfs_layer", "mcm_trace_flip"],
              "_awac_loop": ["awac_winners", "awac_select_augment"]}
    for name, low in lowered.items():
        text = low.as_text(debug_info=True)
        for scope in scopes[name]:
            assert scope in text, (name, scope)
        # the counted cores compile under the phases' module names, which
        # is how a device trace finds each phase
        assert low.as_text().startswith(f"module @jit_{name} "), name


def test_counters_carried_by_the_loops_change_no_result():
    g = graph.generate(128, avg_degree=5.0, kind="antigreedy", seed=2)
    row, col, val = (jnp.asarray(x) for x in (g.row, g.col, g.val))
    st = single.greedy_maximal(row, col, val, g.n)
    st2 = single.mcm(row, col, val, g.n, st.mate_row, st.mate_col)
    row_ptr = row_ptr_from_sorted(row, g.n)
    ws = window_depth(g.n)
    with jax.enable_x64(True):
        s3, it3, counts = single._awac_counted(
            row, col, val, row_ptr, g.n, st2, 1000, MIN_GAIN, "xla", ws)
    s4, it4 = single.awac(row, col, val, g.n, st2, backend="xla",
                          row_ptr=row_ptr, window_steps=ws)
    assert int(it3) == int(it4)
    for a, b in zip(s3, s4):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(counts["awac_augmented"]) >= int(it3) - 1


def _profiled_solves(g, d):
    """Two solves of ``g`` traced into ``d`` inside the benchmark's own
    spans, as ``chipbench.run.Workload.step`` opens them. Returns the
    benchmark's reduction of the trace and the host plane's events as
    (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData

    api.solve(api.MatchingProblem.from_graph(g))  # compiled outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with jax.profiler.trace(d, profiler_options=opts):
        for _ in range(2):
            with jax.profiler.TraceAnnotation(bench_trace.SPANS[0]):
                problem = api.MatchingProblem(row=g.row, col=g.col,
                                              val=g.val, n=g.n)
            with jax.profiler.TraceAnnotation(bench_trace.SPANS[1]):
                result = api.solve(problem)
            with jax.profiler.TraceAnnotation(bench_trace.SPANS[2]):
                np.asarray(result.mate_row)
    (path,) = pathlib.Path(d).glob("**/*.xplane.pb")
    events = [(e.name, e.start_ns, e.end_ns)
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    return bench_trace.load(path), events


def test_spans_share_the_profiler_clock():
    """Two solves traced inside the benchmark's own spans: the benchmark
    still counts two steps, and the host plane holds the program's spans,
    each ``repro.solve`` inside its benchmark ``solve`` span."""
    g = graph.generate(64, avg_degree=4.0, kind="uniform", seed=5)
    with tempfile.TemporaryDirectory() as d:
        tr, events = _profiled_solves(g, d)
    assert len(tr.steps()) == 2
    outer = [e for e in events if e[0] == bench_trace.SPANS[1]]
    roots = [e for e in events if e[0] == "repro.solve"]
    assert len(outer) == len(roots) == 2
    for (_, s0, e0), (_, s1, e1) in zip(sorted(outer, key=lambda e: e[1]),
                                        sorted(roots, key=lambda e: e[1])):
        assert s0 <= s1 and e1 <= e0
    names = {e[0] for e in events}
    assert {"repro.preflight", "repro.greedy", "repro.mcm", "repro.awac",
            "repro.result"} <= names


def test_result_ms_reads_device_idle_inside_the_result_span():
    """The ``result_ms`` reader places each recorded ``repro.result`` span
    on the trace's clock: with device 0 busy everywhere but inside the
    profiler's own ``repro.result`` events it reads their mean length, and
    with device 0 busy throughout it reads 0."""
    g = graph.generate(64, avg_degree=4.0, kind="uniform", seed=6)
    with tempfile.TemporaryDirectory() as d:
        tr, events = _profiled_solves(g, d)
    spans = sorted((s, e) for name, s, e in events if name == "repro.result")
    assert len(spans) == 2
    lo, hi = tr.window
    edges = [lo] + [x for s, e in spans for x in (s, e)] + [hi]
    reader = bench_run.load_reader(bench_run.BENCH_DIR, "result_ms")
    ctx = types.SimpleNamespace(trace=tr, solves=[None, None])
    tr.devices = {0: bench_trace.Device(
        0, [(a, b, "op") for a, b in zip(edges[0::2], edges[1::2])], [])}
    want = sum(e - s for s, e in spans) / 2 / 1e6
    assert reader.read(ctx) == pytest.approx(want, rel=0.02)
    tr.devices = {0: bench_trace.Device(0, [(lo, hi, "op")], [])}
    assert reader.read(ctx) == pytest.approx(0.0, abs=1e-6)
