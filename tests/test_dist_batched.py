"""Differential harness for the distributed-batched AWPM engine (DESIGN.md §5).

Contract under test: ``core.dist.awpm_dist_batched`` is bit-identical per
instance to ``core.batch.awpm_batched`` (itself pinned to
``core.single.awpm``) on every mesh shape — including the per-instance AWAC
iteration counts, with the drop-free ``safe_a2a_caps`` defaults.

Every mesh test runs in a subprocess with 8 fake host devices, because the
device count must be set before jax initializes (see tests/_subproc.py).
The CI ``multi-device`` job runs this file on both jax versions so both
shard_map spellings stay exercised on real multi-device meshes.

In-process tests cover the host-side capacity planning: per-block ``cap``
comes from the TRUE max block occupancy, and an explicit cap below it
raises instead of silently truncating edges.
"""
import numpy as np
import pytest

from _subproc import REPO, run_with_devices

HEADER = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import batch, graph, ref, single
from repro.core.single import MatchState
from repro.core.dist import (DistBatchedAWPM, GridSpec, awpm_dist_batched,
                             make_mesh)


def check_identical(stB, itB, stD, itD, msg=""):
    assert np.array_equal(np.array(itB), np.array(itD)), \
        (msg, np.array(itB), np.array(itD))
    for nm, x, y in zip(("mate_row", "mate_col", "u", "v"), stB, stD):
        assert np.array_equal(np.array(x), np.array(y)), (msg, nm)
"""


# --------------------------------------------------------------------------
# bit-identity across mesh shapes
# --------------------------------------------------------------------------

MESH_SCRIPT = HEADER + r"""
spec = GridSpec(make_mesh({mesh_shape}))
assert (spec.pr, spec.pc) == {mesh_shape}
n = 32
gs = [graph.generate(n, avg_degree=4.0 + (i % 3), kind=k, seed=s)
      for i, (k, s) in enumerate([("uniform", 0), ("antigreedy", 7),
                                  ("circuit", 2), ("banded", 3)])]
row, col, val = batch.stack_graphs(gs)
stB, itB = batch.awpm_batched(row, col, val, n)
assert bool(batch.is_perfect_batched(stB, n).all())
for backend in {backends}:
    stD, itD, dropped = awpm_dist_batched(
        np.array(row), np.array(col), np.array(val), n, spec, backend=backend)
    assert int(dropped) == 0, backend
    check_identical(stB, itB, stD, itD, backend)
print("OK")
"""


@pytest.mark.parametrize("mesh_shape,backends", [
    # 1x1: the block is the instance — also routes the core.batch fused
    # sweep through shard_map
    ((1, 1), ("fused", "xla")),
    ((2, 2), ("fused", "reference")),
    # both 8-device orientations, matching the CI multi-device job
    ((2, 4), ("fused",)),
    ((4, 2), ("fused",)),
], ids=["1x1", "2x2", "2x4", "4x2"])
def test_dist_batched_bit_identical(mesh_shape, backends):
    script = MESH_SCRIPT.format(mesh_shape=mesh_shape, backends=backends)
    out = run_with_devices(script, 8)
    assert "OK" in out


# --------------------------------------------------------------------------
# the grid MCM's sorted-segment parent scan against the plain reference
# --------------------------------------------------------------------------

GRIDS = ("1x1", "1x2", "2x1", "2x2", "pod 2x2x2")

SCAN_SCRIPT = HEADER + r"""
import json, sys
sys.path.insert(0, {repo!r})
from chipbench import gen, reference
from repro.core import api, telemetry

n = 256
# the benchmark's degree-16 instance; a row joined to every column, so one
# block row holds bc entries, heaviest at its first columns, so the scan
# needs all its levels; and a
# sparse antigreedy instance: three sizes, one batch
u = gen.generate(n, 16.0, "uniform", seed=2**31 + 5, capacity=n * 16)
dense = np.r_[u.col[:u.nnz], np.arange(n)]
drow = np.r_[u.row[:u.nnz], np.full(n, 7)]
dval = np.r_[u.val[:u.nnz], 1.0 - np.arange(n) / (2 * n)]
_, first = np.unique(drow.astype(np.int64) * n + dense, return_index=True)
ag = graph.generate(n, avg_degree=3.0, kind="antigreedy", seed=4)
gs = [graph.from_coo(u.row[:u.nnz], u.col[:u.nnz], u.val[:u.nnz], n),
      graph.from_coo(drow[first], dense[first], dval[first], n), ag]
assert len({{g.nnz for g in gs}}) == 3
row, col, val = batch.stack_graphs(gs)
stB, itB = batch.awpm_batched(row, col, val, n)
refs = [reference.solve(np.asarray(g.row), np.asarray(g.col),
                        np.asarray(g.val), n) for g in gs]
meshes = {{
    "1x1": GridSpec(make_mesh((1, 1))), "1x2": GridSpec(make_mesh((1, 2))),
    "2x1": GridSpec(make_mesh((2, 1))), "2x2": GridSpec(make_mesh((2, 2))),
    "pod 2x2x2": GridSpec(jax.make_mesh(
        (2, 2, 2), ("pod", "data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 3),
        ("pod", "data"), "model")}}
out = {{}}
for name, spec in meshes.items():
    got = api.solve(api.MatchingProblem.stack(gs), api.SolveOptions(grid=spec))
    counters = telemetry.recent(1)[0]["counters"]
    mr = np.asarray(got.mate_row)
    out[name] = {{
        "batch_mates_off": int((mr != np.asarray(stB.mate_row)).sum()),
        "batch_rounds": [np.asarray(got.awac_iters).tolist(),
                         np.asarray(itB).tolist()],
        "ref_mates_off": [int((mr[k, :n] != r.mate_row[:n]).sum())
                          for k, r in enumerate(refs)],
        "ref_rounds": [[int(got.awac_iters[k]), r.awac_rounds]
                       for k, r in enumerate(refs)],
        "ref_weight_bits": [
            [np.asarray(got.weight[k], np.float32).tobytes().hex(),
             np.float32(r.weight).tobytes().hex()]
            for k, r in enumerate(refs)],
        "layers": [counters["mcm_bfs_layers"], counters["mcm_sorted_layers"]]}}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def grid_scan_runs():
    import json

    out = run_with_devices(SCAN_SCRIPT.format(repo=str(REPO)), 8)
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("grid", GRIDS)
def test_grid_scan_equals_reference_and_batch(grid_scan_runs, grid):
    """On every grid shape, a batch of three instances of different sizes
    (one with a row of degree n) gives ``chipbench/reference.py``'s mates,
    AWAC rounds and weight bits, and ``core.batch``'s mates and rounds;
    every BFS layer picked its parents with the sorted-segment scan."""
    case = grid_scan_runs[grid]
    assert case["batch_mates_off"] == 0, case
    assert case["batch_rounds"][0] == case["batch_rounds"][1], case
    assert case["ref_mates_off"] == [0, 0, 0], case
    for got, want in case["ref_rounds"] + case["ref_weight_bits"]:
        assert got == want, case
    layers, sorted_layers = case["layers"]
    assert layers > 0 and sorted_layers == layers, case


# --------------------------------------------------------------------------
# mixed per-instance convergence (1 vs ~21 AWAC iterations in one batch)
# --------------------------------------------------------------------------

MIXED_SCRIPT = HEADER + r"""
n = 40
# overlapping heavy 4-cycles: from the diagonal matching, AWAC needs ~n/2
# sequential augmentation rounds (the slow-convergence extreme)
rows, cols, vals = [], [], []
for i in range(n):
    rows.append(i), cols.append(i), vals.append(0.1)
for i in range(n - 1):
    w = 0.5 + 0.4 * i / n
    rows += [i, i + 1]
    cols += [i + 1, i]
    vals += [w, w]
slow = graph.from_coo(np.array(rows, np.int32), np.array(cols, np.int32),
                      np.array(vals, np.float32), n)
fast = graph.generate(n, avg_degree=3.0, kind="circuit", seed=2)
row, col, val = batch.stack_graphs([slow, fast])

# per-instance initial states: diagonal matching for the chain, greedy + MCM
# for the circuit instance (its usual pipeline entry into AWAC)
st_slow = single.state_from_mates(row[0], col[0], val[0], n,
                                  np.arange(n), np.arange(n))
st0 = single.greedy_maximal(row[1], col[1], val[1], n)
st_fast = single.mcm(row[1], col[1], val[1], n, st0.mate_row, st0.mate_col)
stacked = MatchState(*(jnp.stack([a, b]) for a, b in zip(st_slow, st_fast)))

stB, itB = batch.awac_batched(row, col, val, n, stacked)
spec = GridSpec(make_mesh((2, 2)))
drv = DistBatchedAWPM(spec, n)
stD, itD, dropped = drv.run(np.array(row), np.array(col), np.array(val),
                            state=stacked)
assert int(dropped) == 0
check_identical(stB, itB, stD, itD, "mixed")
its = np.array(itD)
assert its[0] >= 20 and its[1] <= 2, its  # genuinely mixed speeds
print("OK")
"""


def test_mixed_convergence_speeds_within_batch():
    """The early finisher's state must stay frozen (bit-exact) on every
    device while the slow instance keeps exchanging and augmenting."""
    out = run_with_devices(MIXED_SCRIPT, 8)
    assert "OK" in out


# --------------------------------------------------------------------------
# degenerate blocks and error paths (one subprocess, 2x2 grid)
# --------------------------------------------------------------------------

DEGENERATE_SCRIPT = HEADER + r"""
spec = GridSpec(make_mesh((2, 2)))

# --- n=1: three of the four devices own only out-of-range padding ---
g1 = graph.from_coo(np.array([0]), np.array([0]), np.array([0.7], np.float32), 1)
row, col, val = batch.stack_graphs([g1, g1])
stB, itB = batch.awpm_batched(row, col, val, 1)
stD, itD, dropped = awpm_dist_batched(np.array(row), np.array(col),
                                      np.array(val), 1, spec)
assert int(dropped) == 0
check_identical(stB, itB, stD, itD, "n1")

# --- empty local blocks: one instance's edges all sit in the two diagonal
# blocks of the 2x2 grid, so its off-diagonal blocks are pure padding ---
n = 16
rows = list(range(n)) + list(range(8)) + list(range(8, 16))
cols = list(range(n)) + [(i + 1) % 8 for i in range(8)] \
    + [8 + (i + 1) % 8 for i in range(8)]
rng = np.random.default_rng(0)
vals = rng.uniform(0.1, 1.0, len(rows)).astype(np.float32)
diag_blocks = graph.from_coo(np.array(rows, np.int32),
                             np.array(cols, np.int32), vals, n)
normal = graph.generate(n, avg_degree=4.0, kind="uniform", seed=1)
row, col, val = batch.stack_graphs([diag_blocks, normal])
stB, itB = batch.awpm_batched(row, col, val, n)
stD, itD, dropped = awpm_dist_batched(np.array(row), np.array(col),
                                      np.array(val), n, spec)
assert int(dropped) == 0
check_identical(stB, itB, stD, itD, "empty-block")

# --- all-ties: every weight equal, only tie-breaks decide ---
gs = []
for seed in (0, 1):
    g0 = graph.generate(n, avg_degree=4.0, kind="uniform", seed=seed,
                        normalize=False)
    real = np.asarray(g0.row) < n
    gs.append(graph.from_coo(np.asarray(g0.row)[real],
                             np.asarray(g0.col)[real],
                             np.full(int(real.sum()), 0.5, np.float32), n))
row, col, val = batch.stack_graphs(gs)
stB, itB = batch.awpm_batched(row, col, val, n)
stD, itD, dropped = awpm_dist_batched(np.array(row), np.array(col),
                                      np.array(val), n, spec)
assert int(dropped) == 0
check_identical(stB, itB, stD, itD, "all-ties")

# --- error paths: unknown backend; local-sweep backends off the 1x1 grid ---
try:
    awpm_dist_batched(np.array(row), np.array(col), np.array(val), n, spec,
                      backend="bogus")
    raise SystemExit("bogus backend did not raise")
except ValueError as e:
    assert "unknown dist AWAC backend" in str(e)
try:
    awpm_dist_batched(np.array(row), np.array(col), np.array(val), n, spec,
                      backend="xla")
    raise SystemExit("xla backend on 2x2 did not raise")
except ValueError as e:
    assert "1x1 grid" in str(e)
print("OK")
"""


def test_degenerate_blocks_and_error_paths():
    out = run_with_devices(DEGENERATE_SCRIPT, 8)
    assert "OK" in out


# --------------------------------------------------------------------------
# consumers: MoE routing and pivot permutations through the dist engine
# --------------------------------------------------------------------------

CONSUMER_SCRIPT = HEADER + r"""
from repro.core import pivot
from repro.models.moe import matching_route_batched

spec = GridSpec(make_mesh((2, 2)))

# pivot: distributed-batched row permutations == local batched ones
rng = np.random.default_rng(0)
mats = [np.diag(rng.uniform(1.0, 2.0, 12)) + rng.uniform(0, 0.2, (12, 12))
        for _ in range(3)]
pL, iL = pivot.batched_pivot_permutations(mats)
pD, iD = pivot.batched_pivot_permutations(mats, mesh=spec)
assert np.array_equal(pL, pD) and np.array_equal(np.array(iL), np.array(iD))

# MoE: all groups routed through the dist engine == the local batched path
g, e, cap, k = 2, 4, 2, 2
t = e * cap
logits = jnp.asarray(rng.standard_normal((g, t, e)).astype(np.float32))
outL = matching_route_batched(logits, k, cap)
outD = matching_route_batched(logits, k, cap, dist_spec=spec)
for nm, a, b in zip(("expert", "slot", "w", "keep", "aux"), outL, outD):
    assert np.array_equal(np.array(a), np.array(b)), nm
print("OK")
"""


def test_consumers_route_through_dist_engine():
    out = run_with_devices(CONSUMER_SCRIPT, 8)
    assert "OK" in out


# --------------------------------------------------------------------------
# hypothesis planted-matching property under the simulated 8-device mesh
# --------------------------------------------------------------------------

HYPOTHESIS_SCRIPT = HEADER + r"""
from hypothesis import given, settings, strategies as st

spec = GridSpec(make_mesh((2, 4)))
n = 16


@st.composite
def planted_batch(draw):
    gs = []
    for _ in range(2):
        deg = draw(st.floats(2.0, 5.0))
        kind = draw(st.sampled_from(
            ["uniform", "circuit", "antigreedy", "banded"]))
        seed = draw(st.integers(0, 10_000))
        gs.append(graph.generate(n, avg_degree=deg, kind=kind, seed=seed))
    return gs


@given(planted_batch())
@settings(max_examples=8, deadline=None)
def prop(gs):
    row, col, val = batch.stack_graphs(gs)
    stD, itD, dropped = awpm_dist_batched(np.array(row), np.array(col),
                                          np.array(val), n, spec)
    assert int(dropped) == 0
    # a perfect matching is planted -> the dist result is perfect and valid
    assert bool(batch.is_perfect_batched(stD, n).all())
    for i, g in enumerate(gs):
        ref.check_matching(g.structure_dense(), np.array(stD.mate_row[i, :n]))
    stB, itB = batch.awpm_batched(row, col, val, n)
    check_identical(stB, itB, stD, itD, "planted")


prop()
print("OK")
"""


def test_planted_matching_property_on_8_devices():
    pytest.importorskip("hypothesis")
    out = run_with_devices(HYPOTHESIS_SCRIPT, 8)
    assert "OK" in out


# --------------------------------------------------------------------------
# in-process: capacity planning from true block occupancy (bugfix)
# --------------------------------------------------------------------------


def _skewed_batch(n=16, cap=40):
    """One dense row (all its edges land in a single grid row) next to a
    uniform instance — the case the old uniform nnz/(pr*pc) estimate
    undercounts."""
    row = np.full((2, cap), n, np.int32)
    col = np.full((2, cap), n, np.int32)
    val = np.zeros((2, cap), np.float32)
    # instance 0: row 0 holds n entries, plus the off-diagonal fill
    r0 = np.concatenate([np.zeros(n, np.int32),
                         np.arange(1, n, dtype=np.int32)])
    c0 = np.concatenate([np.arange(n, dtype=np.int32),
                         np.arange(1, n, dtype=np.int32)])
    order = np.lexsort((c0, r0))
    row[0, : r0.size], col[0, : r0.size] = r0[order], c0[order]
    val[0, : r0.size] = 0.5
    # instance 1: plain diagonal
    row[1, :n] = col[1, :n] = np.arange(n, dtype=np.int32)
    val[1, :n] = 0.5
    return row, col, val


def test_block_cap_from_true_occupancy():
    from repro.sparse.partition import (block_occupancy, plan_block_cap,
                                        partition_coo_2d_batched)

    n = 16
    row, col, val = _skewed_batch(n)
    occ = block_occupancy(row, col, n, 2, 2)
    assert occ.shape == (2, 2, 2)
    # the dense row puts 8 diagonal + 7 fill + 8 dense entries into the two
    # top blocks; the uniform estimate (31 / 4 ~ 8) would truncate
    assert int(occ[0].max()) > (int(occ[0].sum()) + 3) // 4
    cap = plan_block_cap(row, col, n, 2, 2)
    assert cap >= int(occ.max())
    part = partition_coo_2d_batched(row, col, val, n, 2, 2)
    assert part.cap == cap
    # every real edge survives the partition (nothing truncated)
    assert int((part.row < n).sum()) == int((row < n).sum())
    np.testing.assert_array_equal(part.nnz.sum(axis=(0, 1)),
                                  (row < n).sum(axis=1))


def test_partition_refuses_to_truncate():
    from repro.sparse.partition import partition_coo_2d, \
        partition_coo_2d_batched

    n = 16
    row, col, val = _skewed_batch(n)
    with pytest.raises(ValueError, match="refusing to truncate"):
        partition_coo_2d_batched(row, col, val, n, 2, 2, cap=8)
    m = row[0] < n
    with pytest.raises(ValueError, match="refusing to truncate"):
        partition_coo_2d(row[0][m], col[0][m], val[0][m], n, 2, 2, cap=8)
    with pytest.raises(ValueError, match="batched"):
        partition_coo_2d_batched(row[0], col[0], val[0], n, 2, 2)


# --------------------------------------------------------------------------
# in-process: the block order is the plain three-key lexsort's
# --------------------------------------------------------------------------


def _empty_batch(n=16, cap=8):
    """One instance with no real edges: padding alone."""
    return (np.full((1, cap), n, np.int32), np.full((1, cap), n, np.int32),
            np.zeros((1, cap), np.float32))


def _three_sizes(n=50):
    """B = 3 lex-sorted instances of different nnz, padded to one
    capacity; n = 50 leaves the last block row and column short."""
    from repro.core import batch, graph

    gs = [graph.generate(n, avg_degree=d, kind=k, seed=s) for d, k, s in
          ((1.5, "uniform", 1), (4.0, "powerlaw", 2), (9.0, "banded", 3))]
    assert len({g.nnz for g in gs}) == 3
    return tuple(np.asarray(x) for x in batch.stack_graphs(gs))


BATCHES = {"skewed": (_skewed_batch, 16), "no edges": (_empty_batch, 16),
           "three sizes": (_three_sizes, 50)}


def _shuffled(row, col, val):
    """Each instance's slots, padding included, in a random order."""
    rng = np.random.default_rng(11)
    perm = np.stack([rng.permutation(row.shape[1]) for _ in row])
    return tuple(np.take_along_axis(x, perm, axis=1) for x in (row, col, val))


@pytest.mark.parametrize("order", ["sorted", "shuffled"])
@pytest.mark.parametrize("grid", [(1, 1), (2, 2), (2, 4), (4, 2)],
                         ids=lambda g: f"{g[0]}x{g[1]}")
@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_batched_partition_equals_the_lexsort_reference(batch, grid, order):
    """Field by field against a plain ``np.lexsort((col, row, block))`` of
    each instance's real edges: lex-sorted input takes the linear pass and
    counts in ``sorted_instances``; a shuffled copy takes the lexsort
    fallback, counts only where it holds fewer than two edges, and gives
    the same arrays as the sorted input."""
    from repro.sparse.partition import partition_coo_2d_batched

    make, n = BATCHES[batch]
    pr, pc = grid
    row, col, val = sorted_input = make()
    if order == "shuffled":
        row, col, val = _shuffled(row, col, val)
    part = partition_coo_2d_batched(row, col, val, n, pr, pc)
    b = row.shape[0]
    br, bc = -(-n // pr), -(-n // pc)
    counts = np.zeros((b, pr * pc), np.int64)
    for i in range(b):
        m = row[i] < n
        r, c, v = row[i][m], col[i][m], val[i][m]
        blk = (r // br) * pc + c // bc
        ref = np.lexsort((c, r, blk))
        counts[i] = np.bincount(blk, minlength=pr * pc)
        for p in range(pr * pc):
            sel, k = ref[blk[ref] == p], counts[i, p]
            a, q = divmod(p, pc)
            np.testing.assert_array_equal(part.row[a, q, i, :k], r[sel])
            np.testing.assert_array_equal(part.col[a, q, i, :k], c[sel])
            np.testing.assert_array_equal(
                part.val[a, q, i, :k].view(np.uint32), v[sel].view(np.uint32))
            assert (part.row[a, q, i, k:] == n).all()
            assert (part.col[a, q, i, k:] == n).all()
            assert (part.val[a, q, i, k:] == 0).all()
    np.testing.assert_array_equal(
        part.nnz, counts.reshape(b, pr, pc).transpose(1, 2, 0))
    assert part.nnz.dtype == np.int32
    assert part.cap == max(-(-int(counts.max()) // 8) * 8, 8)
    real = (row < n).sum(axis=1)
    assert part.sorted_instances == (b if order == "sorted"
                                     else int((real < 2).sum()))
    if order == "shuffled":
        again = partition_coo_2d_batched(*sorted_input, n, pr, pc)
        for field in ("row", "col", "val", "nnz"):
            assert getattr(part, field).tobytes() == \
                getattr(again, field).tobytes(), field


@pytest.mark.parametrize("shuffled", [(), (0,), (2,), (0, 1, 2)],
                         ids=lambda s: "shuffled " + (",".join(map(str, s))
                                                      or "none"))
def test_partition_sorted_counts_the_sorted_instances(shuffled):
    """The grid driver's partition adds the instances that took the linear
    pass to the solve's ``partition_sorted``; the shuffled ones take the
    fallback and hand the device the same blocks."""
    from repro.core import dist, telemetry

    n = 50
    row, col, val = _three_sizes(n)
    drv = dist._DistBatchedAWPM(dist.GridSpec(dist.make_mesh((1, 1))), n)
    mixed = [x.copy() for x in (row, col, val)]
    for x, s in zip(mixed, _shuffled(row, col, val)):
        x[list(shuffled)] = s[list(shuffled)]
    with telemetry.record():
        part = drv.partition(*mixed)[0]
    assert telemetry.recent(1)[0]["counters"]["partition_sorted"] == \
        3 - len(shuffled)
    base = drv.partition(row, col, val)[0]
    for field in ("row", "col", "val", "nnz"):
        assert getattr(part, field).tobytes() == \
            getattr(base, field).tobytes(), field
