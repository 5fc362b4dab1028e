"""Persistent whole-iteration AWAC kernel + measured dispatch layer.

Contracts under test (ISSUE 7):
  - ``backend="pallas_persistent"`` (the whole AWAC loop — sweeps,
    select/augment, convergence — inside ONE ``pallas_call``) is
    bit-identical to every other local backend: mates, duals, AND iteration
    counts, single and batched, including the max_iter=0 and go0=False
    short-circuits.
  - ``awac_sweep_batched`` rejects an illegal edge tile with a located
    ValueError (not a ``python -O``-strippable assert), and the ops
    wrappers' ``te=None`` clamp small instances UP to one legal tile.
  - ``kernels.backend.resolve_execution`` no longer conflates "not TPU"
    with "interpreter": every compiled-lowering platform resolves to
    ``interpret=False`` and the resolved mode is recorded.
  - ``kernels.dispatch`` (the measured table behind ``backend="auto"``)
    looks up the winner per platform/shape class with the documented
    fallback chain, degrades to None (-> platform heuristic) on a missing
    or corrupt table, and ``MatchResult.execution`` records the honest
    backend/source/interpreter triple.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MatchingProblem, SolveOptions, batch, graph, single, solve
from repro.kernels import backend as kbackend
from repro.kernels import dispatch as kdispatch
from repro.kernels.cycle_gain.awac_sweep import awac_sweep_batched
from repro.kernels.cycle_gain.ops import awac_persistent_loop
from repro.sparse.csr import row_ptr_from_sorted

BACKENDS = ("reference", "xla", "pallas", "pallas_persistent")
KINDS = ["uniform", "circuit", "antigreedy", "banded", "powerlaw"]
STATE_FIELDS = ("mate_row", "mate_col", "u", "v")


def _mcm_state(g):
    row, col, val = jnp.asarray(g.row), jnp.asarray(g.col), jnp.asarray(g.val)
    st = single.greedy_maximal(row, col, val, g.n)
    st = single.mcm(row, col, val, g.n, st.mate_row, st.mate_col)
    return row, col, val, st


def _assert_states_equal(ref, other, msg):
    for nm, a, b in zip(STATE_FIELDS, ref, other):
        np.testing.assert_array_equal(np.array(a), np.array(b),
                                      err_msg=f"{msg}: {nm}")


# --------------------------------------------------------------------------
# tentpole: persistent loop bit-identity (state AND iteration counts)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_persistent_loop_bit_identical(kind):
    g = graph.generate(72, avg_degree=5.0, kind=kind, seed=KINDS.index(kind))
    row, col, val, st = _mcm_state(g)
    sR, iR = single.awac(row, col, val, g.n, st, backend="reference")
    for b in BACKENDS[1:]:
        sB, iB = single.awac(row, col, val, g.n, st, backend=b)
        assert int(iB) == int(iR), f"{kind}: {b} iters {int(iB)} != {int(iR)}"
        _assert_states_equal(sR, sB, f"{kind}: {b}")


def test_persistent_loop_actually_iterates():
    # antigreedy instances force AWAC rounds; the persistent in-kernel
    # while_loop must count them identically to the host loop
    g = graph.generate(96, avg_degree=6.0, kind="antigreedy", seed=11)
    row, col, val, st = _mcm_state(g)
    _, iR = single.awac(row, col, val, g.n, st, backend="reference")
    _, iP = single.awac(row, col, val, g.n, st, backend="pallas_persistent")
    assert int(iR) > 0
    assert int(iP) == int(iR)


def test_persistent_max_iter_zero_is_noop():
    g = graph.generate(40, avg_degree=5.0, kind="antigreedy", seed=3)
    row, col, val, st = _mcm_state(g)
    sP, iP = single.awac(row, col, val, g.n, st, max_iter=0,
                         backend="pallas_persistent")
    assert int(iP) == 0
    _assert_states_equal(st, sP, "max_iter=0")


def test_persistent_go0_false_skips_loop():
    # the degrade-infeasible gate: go0=False must return the input state
    # unchanged with a zero iteration count (whole loop skipped on-chip)
    g = graph.generate(32, avg_degree=5.0, kind="antigreedy", seed=5)
    row, col, val, st = _mcm_state(g)
    rp = row_ptr_from_sorted(row, g.n)
    ws = single._resolve_window_steps(row, g.n, None)
    mr, mc, u, v, it = awac_persistent_loop(
        row, col, val, rp, st.mate_row, st.mate_col, st.u, st.v,
        jnp.float32(1e-6), jnp.array(False), n=g.n, window_steps=ws,
        max_iter=1000)
    assert int(it) == 0
    _assert_states_equal(st, (mr, mc, u, v), "go0=False")


def test_persistent_batched_matches_single_and_xla():
    n = 48
    kinds = [("uniform", 0), ("antigreedy", 7), ("circuit", 2), ("banded", 3)]
    graphs = [graph.generate(n, avg_degree=4.0 + (i % 3), kind=k, seed=s)
              for i, (k, s) in enumerate(kinds)]
    row, col, val = batch.stack_graphs(graphs)
    mr, mc = batch.greedy_maximal_batched(row, col, val, n)
    mr, mc = batch.mcm_batched(row, col, val, n, mr, mc)
    st = batch.state_from_mates_batched(row, col, val, n, mr, mc)
    sX, iX = batch.awac_batched(row, col, val, n, st, backend="xla")
    sP, iP = batch.awac_batched(row, col, val, n, st,
                                backend="pallas_persistent")
    np.testing.assert_array_equal(np.array(iP), np.array(iX))
    _assert_states_equal(sX, sP, "batched")
    # and per instance vs its own single-instance persistent run
    for b in range(len(graphs)):
        st1 = single.MatchState(st.mate_row[b], st.mate_col[b], st.u[b],
                                st.v[b])
        s1, i1 = single.awac(row[b], col[b], val[b], n, st1,
                             backend="pallas_persistent")
        assert int(i1) == int(iP[b])
        for nm, a, bb in zip(STATE_FIELDS, s1, sP):
            np.testing.assert_array_equal(
                np.array(a), np.array(bb[b]), err_msg=f"instance {b}: {nm}")


def test_persistent_small_cap_clamps_up():
    # cap < 128: te=None must clamp up to one legal lane tile (PR 4 padding
    # policy) instead of tripping the divisibility ValueError
    n = 12
    rng = np.random.default_rng(9)
    row = np.repeat(np.arange(n, dtype=np.int32), 3)
    col = np.stack([np.arange(n), (np.arange(n) + 1) % n,
                    (np.arange(n) + 5) % n], axis=1).astype(np.int32).ravel()
    val = rng.uniform(0.1, 1.0, row.size).astype(np.float32)
    g = graph.from_coo(row, col, val, n)
    assert g.capacity < 128
    rowj, colj, valj, st = _mcm_state(g)
    sR, iR = single.awac(rowj, colj, valj, n, st, backend="reference")
    for b in ("pallas", "pallas_persistent"):
        sB, iB = single.awac(rowj, colj, valj, n, st, backend=b)
        assert int(iB) == int(iR)
        _assert_states_equal(sR, sB, f"small-cap {b}")


def test_persistent_invariant_to_tiling_and_forced_interpret():
    # the edge tiling and the execution mode are performance knobs, never
    # semantic ones: every legal te and an explicitly forced interpret flag
    # must produce the same bits as the auto-selected configuration
    g = graph.generate(96, avg_degree=6.0, kind="antigreedy", seed=2)
    row, col, val, st = _mcm_state(g)
    rp = row_ptr_from_sorted(row, g.n)
    ws = single._resolve_window_steps(row, g.n, None)

    def run(**kw):
        return awac_persistent_loop(
            row, col, val, rp, st.mate_row, st.mate_col, st.u, st.v,
            jnp.float32(1e-6), jnp.array(True), n=g.n, window_steps=ws,
            max_iter=1000, **kw)

    base = run()  # te=None (roofline plan), interpret=None (auto)
    for kw in ({"te": 128}, {"te": 256}, {"interpret": True},
               {"te": 128, "interpret": True}):
        other = run(**kw)
        assert int(other[4]) == int(base[4]), kw
        _assert_states_equal(base[:4], other[:4], f"variant {kw}")


# --------------------------------------------------------------------------
# satellite: the bare-assert bugfix (awac_sweep_batched tile check)
# --------------------------------------------------------------------------


def test_sweep_rejects_illegal_tile_with_valueerror():
    n, cap, b = 8, 256, 1
    row = jnp.full((b, cap), n, jnp.int32)
    col = jnp.full((b, cap), n, jnp.int32)
    val = jnp.zeros((b, cap), jnp.float32)
    rp = jnp.zeros((b, n + 2), jnp.int32)
    mates = jnp.full((b, n + 1), n, jnp.int32)
    duals = jnp.zeros((b, n + 1), jnp.float32)
    for te in (64, 100, 192):  # not a x128 multiple / doesn't divide cap
        with pytest.raises(ValueError, match="multiple of 128"):
            awac_sweep_batched(row, col, val, rp, mates, mates, duals, duals,
                               jnp.float32(1e-6), n=n, te=te,
                               window_steps=3, interpret=True)


def test_persistent_rejects_illegal_tile_with_valueerror():
    g = graph.generate(16, avg_degree=3.0, kind="uniform", seed=0)
    row, col, val, st = _mcm_state(g)
    rp = row_ptr_from_sorted(row, g.n)
    with pytest.raises(ValueError, match="128"):
        awac_persistent_loop(row, col, val, rp, st.mate_row, st.mate_col,
                             st.u, st.v, jnp.float32(1e-6), jnp.array(True),
                             n=g.n, window_steps=3, max_iter=4, te=100)


# --------------------------------------------------------------------------
# satellite: resolve_execution (non-TPU != interpreter)
# --------------------------------------------------------------------------


def test_resolve_execution_per_platform(monkeypatch):
    for plat, expect in [("cpu", True), ("tpu", False), ("gpu", False),
                         ("cuda", False), ("rocm", False)]:
        monkeypatch.setattr(jax, "default_backend", lambda p=plat: p)
        mode = kbackend.resolve_execution(None)
        assert mode.interpret is expect, (plat, mode)
        assert mode.platform == plat
        assert mode.forced is False
        assert mode.ran_interpreted is expect
        assert mode.describe() == f"interpret={expect}"


def test_resolve_execution_explicit_wins_and_is_recorded(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kbackend.resolve_interpret(True) is True
    last = kbackend.last_execution()
    assert last.forced is True and last.interpret is True
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert kbackend.resolve_interpret(False) is False
    assert kbackend.last_execution().forced is True


def test_kernel_wrappers_record_last_execution():
    g = graph.generate(24, avg_degree=4.0, kind="uniform", seed=1)
    row, col, val, st = _mcm_state(g)
    single.awac(row, col, val, g.n, st, backend="pallas")
    last = kbackend.last_execution()
    assert last is not None
    assert last.platform == jax.default_backend()
    expect = jax.default_backend() not in kbackend.COMPILED_PLATFORMS
    assert last.interpret is expect


# --------------------------------------------------------------------------
# satellite: measured dispatch table behind backend="auto"
# --------------------------------------------------------------------------


def test_dispatch_shape_class():
    assert kdispatch.shape_class(None) == "single_large"
    assert kdispatch.shape_class(kdispatch.SMALL_N) == "single_small"
    assert kdispatch.shape_class(kdispatch.SMALL_N + 1) == "single_large"
    assert kdispatch.shape_class(64, batch=1) == "single_small"
    assert kdispatch.shape_class(64, batch=2) == "batched_small"
    assert kdispatch.shape_class(None, batch=8) == "batched_large"


def test_dispatch_lookup_and_fallback_chain(tmp_path):
    p = tmp_path / "table.json"
    kdispatch.save_table(
        {"cpu/single_small": {"winner": "xla",
                              "us_per_iter": {"xla": 1.0, "reference": 2.0}},
         "cpu/batched_large": {"winner": "pallas_persistent",
                               "us_per_iter": {"pallas_persistent": 1.0}}},
        {"note": "unit fixture"}, p)
    # exact class hits
    assert kdispatch.choose_backend(n=16, platform="cpu", path=p) == "xla"
    assert kdispatch.choose_backend(n=512, batch=4, platform="cpu",
                                    path=p) == "pallas_persistent"
    # same-kind fallback: single_large -> single_small measurement
    assert kdispatch.choose_backend(n=512, platform="cpu", path=p) == "xla"
    # same-kind fallback: batched_small -> batched_large measurement
    assert kdispatch.choose_backend(n=16, batch=4, platform="cpu",
                                    path=p) == "pallas_persistent"
    # unmeasured platform: None, never a guess
    assert kdispatch.choose_backend(n=16, platform="tpu", path=p) is None
    kdispatch.clear_cache()


def test_dispatch_missing_or_corrupt_table_degrades_to_none(tmp_path):
    kdispatch.clear_cache()
    missing = tmp_path / "nope.json"
    assert kdispatch.choose_backend(n=16, platform="cpu", path=missing) is None
    corrupt = tmp_path / "bad.json"
    corrupt.write_text("{not json", encoding="utf-8")
    assert kdispatch.choose_backend(n=16, platform="cpu", path=corrupt) is None
    wrong_shape = tmp_path / "wrong.json"
    wrong_shape.write_text(json.dumps({"entries": []}), encoding="utf-8")
    assert kdispatch.choose_backend(n=16, platform="cpu",
                                    path=wrong_shape) is None
    empty_winner = tmp_path / "empty.json"
    empty_winner.write_text(json.dumps(
        {"entries": {"cpu/single_small": {"winner": "",
                                          "us_per_iter": {}}}}),
        encoding="utf-8")
    assert kdispatch.choose_backend(n=16, platform="cpu",
                                    path=empty_winner) is None
    kdispatch.clear_cache()


def test_resolve_backend_consults_table_then_heuristic(tmp_path, monkeypatch):
    plat = jax.default_backend()
    p = tmp_path / "t.json"
    kdispatch.save_table(
        {f"{plat}/single_small": {"winner": "reference",
                                  "us_per_iter": {"reference": 1.0}}},
        {}, p)
    monkeypatch.setenv(kdispatch.TABLE_ENV_VAR, str(p))
    kdispatch.clear_cache()
    assert single.resolve_backend("auto", n=16) == "reference"
    # explicit backends pass through untouched
    assert single.resolve_backend("pallas_persistent") == "pallas_persistent"
    # no table -> the labeled heuristic
    monkeypatch.setenv(kdispatch.TABLE_ENV_VAR, str(tmp_path / "absent.json"))
    kdispatch.clear_cache()
    heur = single.resolve_backend("auto", n=16)
    assert heur == "xla"
    kdispatch.clear_cache()


def test_committed_table_routes_auto_to_measured_winner():
    # the acceptance check: on a platform the committed BENCH_dispatch.json
    # covers, backend="auto" must route to that measured winner
    table = kdispatch.load_table(kdispatch.DEFAULT_TABLE_PATH)
    assert table is not None, "BENCH_dispatch.json must be committed"
    plat = jax.default_backend()
    key = f"{plat}/single_large"
    if key not in table["entries"]:
        pytest.skip(f"no committed measurements for platform {plat!r}")
    entry = table["entries"][key]
    winner = entry["winner"]
    assert winner == min(entry["us_per_iter"], key=entry["us_per_iter"].get)
    assert single.resolve_backend("auto", n=2048) == winner
    # honest labeling: pallas rows on interpreter-only platforms say so
    for b, flag in entry.get("interpret", {}).items():
        assert flag is (plat not in kbackend.COMPILED_PLATFORMS), (b, flag)


# --------------------------------------------------------------------------
# satellite: MatchResult.execution (honest dispatch record) + api guards
# --------------------------------------------------------------------------


def _problem(n=24):
    g = graph.generate(n, avg_degree=4.0, kind="uniform", seed=0)
    return MatchingProblem(row=g.row, col=g.col, val=g.val, n=g.n)


def test_solve_records_explicit_execution():
    prob = _problem()
    res = solve(prob, SolveOptions(backend="reference"))
    assert res.execution.backend == "reference"
    assert res.execution.source == "explicit"
    assert res.execution.ran_interpreted is False


@pytest.mark.parametrize("backend", ["pallas", "pallas_persistent"])
def test_solve_records_interpreter_flag(backend):
    prob = _problem()
    res = solve(prob, SolveOptions(backend=backend))
    assert res.execution.backend == backend
    expect = jax.default_backend() not in kbackend.COMPILED_PLATFORMS
    assert res.execution.ran_interpreted is expect


def test_solve_records_table_vs_heuristic_source(tmp_path, monkeypatch):
    prob = _problem()
    plat = jax.default_backend()
    p = tmp_path / "t.json"
    kdispatch.save_table(
        {f"{plat}/single_small": {"winner": "xla",
                                  "us_per_iter": {"xla": 1.0}}}, {}, p)
    monkeypatch.setenv(kdispatch.TABLE_ENV_VAR, str(p))
    kdispatch.clear_cache()
    res = solve(prob, SolveOptions(backend="auto"))
    assert res.execution.backend == "xla"
    assert res.execution.source == "table"
    monkeypatch.setenv(kdispatch.TABLE_ENV_VAR, str(tmp_path / "absent.json"))
    kdispatch.clear_cache()
    res = solve(prob, SolveOptions(backend="auto"))
    assert res.execution.source == "heuristic"
    assert res.execution.backend == "xla"
    kdispatch.clear_cache()


def test_solve_persistent_backend_end_to_end():
    prob = _problem(n=40)
    ref = solve(prob, SolveOptions(backend="reference"))
    per = solve(prob, SolveOptions(backend="pallas_persistent"))
    np.testing.assert_array_equal(np.array(ref.mate_row),
                                  np.array(per.mate_row))
    np.testing.assert_array_equal(np.array(ref.mate_col),
                                  np.array(per.mate_col))
    assert int(ref.awac_iters) == int(per.awac_iters)
    assert float(ref.weight) == float(per.weight)
    assert bool(per.perfect)


def test_persistent_backend_rejects_grid():
    from repro.core.dist import make_mesh

    with pytest.raises(ValueError, match="pallas_persistent"):
        SolveOptions(backend="pallas_persistent", grid=make_mesh((1, 1)))
