"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler is installed with jax, so ``jit(...).lower(shapes)
.compile()`` against a described ``v5e:2x2`` topology raises whatever the
chip's compiler would raise, with no chip present. Covered: the local XLA
route (greedy, MCM, the AWAC loop under x64), the batched route and the
2x2 ``shard_map`` grid engine.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and every
test worker imports this file.
"""
import importlib.util
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

from repro.core import batch, dist, single
from repro.core.constants import MIN_GAIN
from repro.sparse.csr import window_depth

DEG = 16
# MCM's sorted-segment scan levels for rows of up to 64 entries: the
# benchmark's degree-16 instance at n = 65,536 has 36 at most
MCM_LEVELS = 6


@pytest.fixture(scope="module")
def topo():
    topologies = pytest.importorskip("jax.experimental.topologies")
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler: libtpu is not installed")

    # with libtpu installed, a failure to describe the chip is a fault
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        # a described chip's executables cannot be read back from the
        # persistent cache, so keep these compiles out of it
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, shape, n):
    """(row, col, val, row_ptr, MatchState) shape structs for [*shape]
    edge arrays over n rows."""
    lead = shape[:-1]

    def s(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=sharding)

    edges = (s(shape, jnp.int32), s(shape, jnp.int32),
             s(shape, jnp.float32))
    state = single.MatchState(
        s(lead + (n + 1,), jnp.int32), s(lead + (n + 1,), jnp.int32),
        s(lead + (n + 1,), jnp.float32), s(lead + (n + 1,), jnp.float32))
    return edges, s(lead + (n + 2,), jnp.int32), state


def _lower_phase(mod, phase, n, edges, row_ptr, st):
    """Lower one phase of ``mod``'s local XLA route (``single`` or
    ``batch``), counters included, as ``solve()`` runs it; AWAC runs under
    x64, as ``single.awac`` runs it."""
    row, col, val = edges
    if phase == "greedy":
        fn = single._greedy_counted if mod is single \
            else batch._greedy_maximal_batched
        return fn.lower(row, col, val, n=n)
    if phase == "mcm" and mod is single:
        return single._mcm_counted.lower(row, col, val, row_ptr, n,
                                         st.mate_row, st.mate_col,
                                         MCM_LEVELS)
    if phase == "mcm":
        return batch._mcm_batched.lower(row, col, val, n, st.mate_row,
                                        st.mate_col)
    fn = single._awac_counted if mod is single else batch._awac_loop_batched
    with jax.enable_x64(True):
        return fn.lower(row, col, val, row_ptr, n, st, 1000, MIN_GAIN,
                        "xla", window_depth(n))


@pytest.mark.parametrize("phase", ["greedy", "mcm", "awac"])
def test_local_xla_route_compiles(one_chip, phase):
    n = 65_536
    edges, row_ptr, st = _shapes(one_chip, (n * DEG,), n)
    compiled = _lower_phase(single, phase, n, edges, row_ptr, st).compile()
    assert compiled.memory_analysis() is not None


def _shape_of(text, name):
    """The result shape of instruction ``%name`` in HLO ``text``."""
    return re.search(rf"%{re.escape(name)} = (\S+) ", text).group(1)


def test_mcm_bfs_layer_reads_the_edges_once(one_chip):
    """MCM's BFS layer, compiled for the chip at the benchmark's size,
    holds one edge-wide gather, ``frontier[col]``, and no scatter whose
    updates span the edges: each row's parent comes from a scan over its
    contiguous run of edges, not from scatters into the rows."""
    n = 65_536
    cap = n * DEG
    edges, row_ptr, st = _shapes(one_chip, (cap,), n)
    text = _lower_phase(single, "mcm", n, edges, row_ptr, st).compile() \
        .as_text()
    layer = [ln for ln in text.splitlines() if "mcm_bfs_layer/" in ln]
    gathers = [ln for ln in layer if " gather(" in ln]
    edge_gathers = [ln for ln in gathers
                    if re.search(rf"= \w+\[{cap}\]", ln)]
    assert len(gathers) >= 2 and len(edge_gathers) == 1, edge_gathers
    assert edge_gathers[0].split("=")[1].strip().startswith("pred["), \
        edge_gathers[0]  # the frontier's flags, read at each edge's column
    scatters = [ln for ln in layer if " scatter(" in ln]
    for ln in scatters:
        updates = re.search(r" scatter\(%[^,]+, %[^,]+, %([^,)]+)", ln)
        assert f"[{cap}]" not in _shape_of(text, updates.group(1)), ln


@pytest.mark.parametrize("phase", ["greedy", "mcm", "awac"])
def test_batched_route_compiles(one_chip, phase):
    b, n = 32, 1024
    edges, row_ptr, st = _shapes(one_chip, (b, n * DEG), n)
    _lower_phase(batch, phase, n, edges, row_ptr, st).compile()


@pytest.fixture(scope="module")
def grid_2x2(topo):
    """The whole distributed-batched engine (greedy, MCM and AWAC in one
    ``shard_map``) compiled over the four described chips, and its block
    capacity. Its program is large, so the instance is small: the compile
    takes about half a minute."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    spec = dist.GridSpec(mesh)
    n, b = 512, 1
    cap = n * DEG // 4 * 5 // 4  # a quarter of the edges, 25% headroom
    run = dist._make_awpm_dist_batched(
        spec, n, b, cap, dist.safe_a2a_caps(cap, 2, 2), 1000, MIN_GAIN,
        backend="fused", window_steps=window_depth(cap),
        degrade_infeasible=True)
    blocks = NamedSharding(mesh, spec.block_spec_batched())
    args = [jax.ShapeDtypeStruct((2, 2, b, cap), dt, sharding=blocks)
            for dt in (jnp.int32, jnp.int32, jnp.float32)]
    with jax.enable_x64(True):
        return cap, run.lower(*args).compile().as_text()


def test_grid_engine_compiles_on_2x2(grid_2x2):
    _, text = grid_2x2
    assert "all-gather" in text


def _dims(shape):
    """Every dimension of an HLO shape (a tuple's too)."""
    return [int(d) for dims in re.findall(r"\[([\d,]*)\]", shape)
            for d in dims.split(",") if d]


def test_grid_mcm_bfs_layer_scatters_no_edge_block(grid_2x2):
    """The grid's BFS layer, compiled for the described 2x2, reads the edge
    block once, in the ``frontier[bcol]`` gather, and holds no scatter with
    an operand that spans the block: each row's parent comes from the
    sorted-segment scan over its run of edges, not from a scatter of the
    block's edges into the rows."""
    cap, text = grid_2x2
    layer = [ln for ln in text.splitlines() if "mcm_bfs_layer/" in ln]
    edge_gathers = [ln for ln in layer if " gather(" in ln
                    and cap in _dims(ln.split("=")[1].split(" gather(")[0])]
    assert len(edge_gathers) == 1, edge_gathers
    assert edge_gathers[0].split("=")[1].strip().startswith("pred["), \
        edge_gathers[0]  # the frontier's flags, read at each edge's column
    scatters = [ln for ln in layer if " scatter(" in ln]
    assert scatters  # bfs_commit's frontier, over the rows
    for ln in scatters:
        operands = re.search(r" scatter\(([^)]*)\)", ln).group(1)
        for name in re.findall(r"%([\w.\-]+)", operands):
            assert cap not in _dims(_shape_of(text, name)), (name, ln)
