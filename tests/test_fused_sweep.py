"""Fused AWAC sweep engine vs the seed reference — bit-identical winners.

Covers the three Step-C backends on padded COO instances:
  * reference  — seed jnp path (global lex search + two-pass reductions)
  * xla        — CSR-windowed lookup + packed-key one-pass segment_max
  * pallas     — fused ``awac_sweep`` kernel (interpret mode on CPU)
including gain ties, the all-padding instance, and the no-candidate case.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph, single
from repro.kernels.cycle_gain.ops import awac_sweep_winners
from repro.sparse.csr import max_row_nnz, row_ptr_from_sorted, window_depth
from repro.sparse.ops import segment_max_with_payload

KINDS = ["uniform", "circuit", "antigreedy", "banded", "powerlaw"]


def _mcm_state(g):
    row, col, val = jnp.asarray(g.row), jnp.asarray(g.col), jnp.asarray(g.val)
    st = single.greedy_maximal(row, col, val, g.n)
    st = single.mcm(row, col, val, g.n, st.mate_row, st.mate_col)
    return row, col, val, st


def _winners_all_backends(row, col, val, n, st, min_gain=1e-6):
    rp = row_ptr_from_sorted(row, n)
    ws = window_depth(max_row_nnz(row, n))
    ref = single.awac_cwinners(row, col, val, n, st, min_gain)
    xla = single.awac_cwinners_fused(row, col, val, rp, n, st, min_gain, ws)
    with jax.enable_x64(True):  # packed-key one-pass reduction branch
        xla64 = single.awac_cwinners_fused(row, col, val, rp, n, st,
                                           min_gain, ws)
    pal = awac_sweep_winners(row, col, val, rp, st.mate_row, st.mate_col,
                             st.u, st.v, jnp.float32(min_gain), n=n,
                             window_steps=ws, te=128)
    return ref, xla, xla64, pal


def _assert_identical(ref, others, msg):
    names = ["Cgain", "Ci", "Cw1", "Cw2"]
    for tag, other in others.items():
        for nm, a, b in zip(names, ref, other):
            np.testing.assert_array_equal(
                np.array(a), np.array(b), err_msg=f"{msg}: {tag} {nm}")


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_cwinners_bit_identical_across_backends(kind, seed):
    g = graph.generate(72, avg_degree=5.0, kind=kind, seed=seed)
    row, col, val, st = _mcm_state(g)
    ref, xla, xla64, pal = _winners_all_backends(row, col, val, g.n, st)
    _assert_identical(ref, {"xla": xla, "xla-packed": xla64, "pallas": pal},
                      f"{kind}/{seed}")


def test_cwinners_with_gain_ties():
    # quantized weights force exact f32 gain ties across different rows of
    # the same column; the smallest-row tie-break must agree everywhere
    n = 32
    g0 = graph.generate(n, avg_degree=6.0, kind="uniform", seed=3,
                        normalize=False)
    real = np.asarray(g0.row) < n
    val = (np.round(np.asarray(g0.val)[real] * 4) / 4 + 0.25).astype(np.float32)
    g = graph.from_coo(np.asarray(g0.row)[real], np.asarray(g0.col)[real],
                       val, n)
    row, col, val, st = _mcm_state(g)
    ref, xla, xla64, pal = _winners_all_backends(row, col, val, g.n, st)
    _assert_identical(ref, {"xla": xla, "xla-packed": xla64, "pallas": pal},
                      "ties")


def test_cwinners_all_padding():
    n = 16
    cap = 48
    row = jnp.full((cap,), n, jnp.int32)
    col = jnp.full((cap,), n, jnp.int32)
    val = jnp.zeros((cap,), jnp.float32)
    st = single.empty_state(n)
    ref, xla, xla64, pal = _winners_all_backends(row, col, val, n, st)
    _assert_identical(ref, {"xla": xla, "xla-packed": xla64, "pallas": pal},
                      "all-padding")
    assert np.all(np.isneginf(np.array(ref[0])))
    assert np.all(np.array(ref[1]) == n)


def test_cwinners_no_candidates():
    # perfect diagonal matching with no off-diagonal edges: no 4-cycles
    n = 12
    row = np.arange(n, dtype=np.int32)
    col = np.arange(n, dtype=np.int32)
    val = np.linspace(0.5, 1.0, n).astype(np.float32)
    g = graph.from_coo(row, col, val, n)
    rowj, colj, valj = jnp.asarray(g.row), jnp.asarray(g.col), jnp.asarray(g.val)
    st = single.state_from_mates(rowj, colj, valj, n, np.arange(n),
                                 np.arange(n))
    ref, xla, xla64, pal = _winners_all_backends(rowj, colj, valj, n, st)
    _assert_identical(ref, {"xla": xla, "xla-packed": xla64, "pallas": pal},
                      "no-candidates")
    assert np.all(np.isneginf(np.array(ref[0])))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_full_awac_loop_matches_reference(backend):
    g = graph.generate(64, avg_degree=6.0, kind="antigreedy", seed=11)
    row, col, val = jnp.asarray(g.row), jnp.asarray(g.col), jnp.asarray(g.val)
    st = single.greedy_maximal(row, col, val, g.n)
    st = single.mcm(row, col, val, g.n, st.mate_row, st.mate_col)
    sR, iR = single.awac(row, col, val, g.n, st, backend="reference")
    sB, iB = single.awac(row, col, val, g.n, st, backend=backend)
    assert int(iR) == int(iB)
    for a, b in zip(sR, sB):
        np.testing.assert_array_equal(np.array(a), np.array(b))


def test_packed_segment_max_matches_two_pass():
    rng = np.random.default_rng(4)
    m, ns = 4000, 129
    vals = jnp.asarray(np.round(rng.uniform(-1, 1, m), 2), jnp.float32)
    vals = vals.at[:50].set(-jnp.inf)  # explicit -inf entries
    pay = jnp.asarray(rng.integers(0, 1 << 20, m), jnp.int32)
    seg = jnp.asarray(rng.integers(0, ns + 1, m), jnp.int32)  # incl. dump seg
    g1, p1 = segment_max_with_payload(vals, pay, seg, ns + 1)
    with jax.enable_x64(True):
        g2, p2 = segment_max_with_payload(vals, pay, seg, ns + 1)
    np.testing.assert_array_equal(np.array(g1), np.array(g2))
    np.testing.assert_array_equal(np.array(p1), np.array(p2))
