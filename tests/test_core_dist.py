"""Distributed AWPM (shard_map, 2D grid) vs single-device — bit-identical.

Runs in subprocesses because the fake device count must be set before jax
initializes (see DESIGN.md; the dry-run has the same constraint)."""
import pytest

from _subproc import run_with_devices

DIST_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import graph, ref, single
from repro.core.dist import GridSpec, DistAWPM, default_caps

from jax.sharding import AxisType
mesh = jax.make_mesh({mesh_shape}, {mesh_axes}, axis_types=(AxisType.Auto,)*{nax})
spec = GridSpec(mesh, {row_axes}, "model")
for seed in range(3):
    g = graph.generate(64, avg_degree=6.0, kind="{kind}", seed=seed)
    struct = g.structure_dense()
    caps = default_caps(g.n, g.nnz, spec.pr, spec.pc, slack=8.0)
    drv = DistAWPM(spec, g.n, cap=((g.nnz // (spec.pr*spec.pc) + 63)//64*64 + 64),
                   a2a_caps=caps)
    st, iters, dropped = drv.run(g)
    assert int(dropped) == 0
    mrD = np.array(st.mate_row[:g.n])
    ref.check_matching(struct, mrD)
    assert ref.is_perfect(mrD, g.n)
    stS, _ = single.awpm(jnp.asarray(g.row), jnp.asarray(g.col), jnp.asarray(g.val), g.n)
    assert np.array_equal(mrD, np.array(stS.mate_row[:g.n])), "dist != single"
print("OK")
"""


@pytest.mark.parametrize("kind", ["uniform", "antigreedy"])
def test_dist_awpm_4x4_matches_single(kind):
    script = DIST_SCRIPT.format(
        mesh_shape="(4, 4)", mesh_axes='("data", "model")', nax=2,
        row_axes='("data",)', kind=kind,
    )
    out = run_with_devices(script, 16)
    assert "OK" in out


def test_dist_awpm_multipod_matches_single():
    script = DIST_SCRIPT.format(
        mesh_shape="(2, 2, 4)", mesh_axes='("pod", "data", "model")', nax=3,
        row_axes='("pod", "data")', kind="uniform",
    )
    out = run_with_devices(script, 16)
    assert "OK" in out


OVERFLOW_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import graph, ref, single
from repro.core.dist import GridSpec, DistAWPM

from jax.sharding import AxisType
mesh = jax.make_mesh((4, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
spec = GridSpec(mesh, ("data",), "model")
g = graph.generate(64, avg_degree=8.0, kind="uniform", seed=5)
struct = g.structure_dense()
# deliberately tiny bucket capacities -> overflow; result must STILL be a
# valid perfect matching (dropped candidates just delay augmentations)
drv = DistAWPM(spec, g.n, cap=((g.nnz // 16 + 63)//64*64 + 64), a2a_caps=(4, 4))
st, iters, dropped = drv.run(g)
mr = np.array(st.mate_row[:g.n])
ref.check_matching(struct, mr)
assert ref.is_perfect(mr, g.n)
w = float(single.matching_weight(st, g.n))
dense = g.to_dense().astype(np.float32)
_, opt = ref.exact_mwpm(dense, struct)
assert w >= 0.5 * opt  # still a heavy matching even with drops
print("OK dropped=", int(dropped))
"""


def test_dist_awpm_overflow_safe():
    out = run_with_devices(OVERFLOW_SCRIPT, 16)
    assert "OK" in out
