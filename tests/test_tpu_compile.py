"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler is installed with jax, so ``jit(...).lower(shapes)
.compile()`` against a described ``v5e:2x2`` topology raises whatever the
chip's compiler would raise, with no chip present. Covered: the local XLA
route (greedy, MCM, the AWAC loop under x64), the batched route, the 2x2
``shard_map`` grid engine, and the two Pallas sweeps, which Mosaic refuses
today (strict xfails: the change that makes them compile must flip them).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and every
test worker imports this file.
"""
import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

from repro.core import batch, dist, single
from repro.core.constants import MIN_GAIN
from repro.sparse.csr import window_depth

DEG = 16


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
    ``batch``); AWAC runs under x64, as ``single.awac`` runs it."""
    row, col, val = edges
    if phase == "greedy":
        fn = single.greedy_maximal if mod is single \
            else batch._greedy_maximal_batched
        return fn.lower(row, col, val, n=n)
    if phase == "mcm":
        fn = single.mcm if mod is single else batch._mcm_batched
        return fn.lower(row, col, val, n, st.mate_row, st.mate_col)
    fn = single._awac_loop if mod is single else batch._awac_loop_batched
    with jax.enable_x64(True):
        return fn.lower(row, col, val, row_ptr, n, st, 1000, MIN_GAIN,
                        "xla", window_depth(n))


@pytest.mark.parametrize("phase", ["greedy", "mcm", "awac"])
def test_local_xla_route_compiles(one_chip, phase):
    n = 65_536
    edges, row_ptr, st = _shapes(one_chip, (n * DEG,), n)
    compiled = _lower_phase(single, phase, n, edges, row_ptr, st).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("phase", ["greedy", "mcm", "awac"])
def test_batched_route_compiles(one_chip, phase):
    b, n = 32, 1024
    edges, row_ptr, st = _shapes(one_chip, (b, n * DEG), n)
    _lower_phase(batch, phase, n, edges, row_ptr, st).compile()


def test_grid_engine_compiles_on_2x2(topo):
    """The whole distributed-batched engine (greedy, MCM and AWAC in one
    ``shard_map``) over the four described chips. Its program is large,
    so the instance is small: the compile takes about half a minute."""
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
        compiled = run.lower(*args).compile()
    assert "all-gather" in compiled.as_text()


def _sweep_args(one_chip, n=2048, cap=16_384):
    (row, col, val), row_ptr, st = _shapes(one_chip, (1, cap), n)
    return (row, col, val, row_ptr, *st,
            jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip))


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason="Mosaic: 'Only 2D gather is supported' — the "
                          "awac_sweep kernel's 1-D gathers have no TPU "
                          "lowering")
def test_pallas_sweep_compiles(one_chip):
    from repro.kernels.cycle_gain.awac_sweep import awac_sweep_batched

    jax.jit(lambda *a: awac_sweep_batched(
        *a, n=2048, te=512, window_steps=5, interpret=False)).lower(
        *_sweep_args(one_chip)).compile()


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason="Mosaic: 'Unimplemented primitive in Pallas TPU "
                          "lowering for KernelType.TC: dynamic_slice' — the "
                          "persistent kernel's tile loop")
def test_pallas_persistent_compiles(one_chip):
    from repro.kernels.cycle_gain.persistent import awac_persistent_batched

    go0 = jax.ShapeDtypeStruct((1,), jnp.bool_, sharding=one_chip)
    jax.jit(lambda *a: awac_persistent_batched(
        *a, n=2048, te=512, window_steps=5, max_iter=10,
        interpret=False)).lower(*_sweep_args(one_chip), go0).compile()
