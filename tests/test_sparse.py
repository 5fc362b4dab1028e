"""Sparse substrate unit tests."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sparse import (
    coo_sddmm,
    coo_spmm,
    coo_to_padded_csr,
    partition_coo_2d,
    segment_max_with_payload,
    segment_softmax,
)
from repro.sparse.ops import segment_argmax_tie


def _rand_coo(n, m, seed=0):
    rng = np.random.default_rng(seed)
    rr = rng.integers(0, n, m).astype(np.int32)
    cc = rng.integers(0, n, m).astype(np.int32)
    key = rr.astype(np.int64) * n + cc
    _, idx = np.unique(key, return_index=True)
    rr, cc = rr[idx], cc[idx]
    vv = rng.uniform(0.1, 1.0, rr.shape[0]).astype(np.float32)
    return rr, cc, vv


def test_coo_spmm_matches_dense():
    n = 37
    rr, cc, vv = _rand_coo(n, 200)
    x = np.random.default_rng(1).normal(size=(n, 8)).astype(np.float32)
    a = np.zeros((n, n), np.float32)
    a[rr, cc] = vv
    # pad
    pad = 17
    row = jnp.concatenate([jnp.asarray(rr), jnp.full((pad,), n, jnp.int32)])
    col = jnp.concatenate([jnp.asarray(cc), jnp.full((pad,), n, jnp.int32)])
    val = jnp.concatenate([jnp.asarray(vv), jnp.zeros((pad,), jnp.float32)])
    xj = jnp.concatenate([jnp.asarray(x), jnp.zeros((1, 8), jnp.float32)])
    y = coo_spmm(row, col, val, xj, n)
    np.testing.assert_allclose(np.array(y), a @ x, rtol=1e-5, atol=1e-5)


def test_coo_sddmm():
    n = 19
    rr, cc, _ = _rand_coo(n, 80)
    a = np.random.default_rng(2).normal(size=(n, 6)).astype(np.float32)
    b = np.random.default_rng(3).normal(size=(n, 6)).astype(np.float32)
    out = coo_sddmm(jnp.asarray(rr), jnp.asarray(cc), jnp.asarray(a), jnp.asarray(b))
    expect = (a @ b.T)[rr, cc]
    np.testing.assert_allclose(np.array(out), expect, rtol=1e-5, atol=1e-5)


def test_segment_softmax_sums_to_one():
    seg = jnp.array([0, 0, 1, 1, 1, 3], jnp.int32)
    logits = jnp.array([0.5, -1.0, 2.0, 2.0, 0.0, 5.0], jnp.float32)
    p = segment_softmax(logits, seg, 4)
    sums = np.zeros(4)
    np.add.at(sums, np.array(seg), np.array(p))
    np.testing.assert_allclose(sums[[0, 1, 3]], 1.0, rtol=1e-6)


def test_segment_max_with_payload_ties():
    vals = jnp.array([1.0, 2.0, 2.0, 0.5], jnp.float32)
    seg = jnp.array([0, 0, 0, 1], jnp.int32)
    payload = jnp.array([10, 7, 3, 2], jnp.int32)
    m, p = segment_max_with_payload(vals, payload, seg, 3)
    assert float(m[0]) == 2.0 and int(p[0]) == 3  # tie -> smaller payload
    assert int(p[2]) == -1  # empty segment


def test_segment_argmax_tie_key():
    vals = jnp.array([2.0, 2.0, 1.0], jnp.float32)
    tie = jnp.array([5, 3, 1], jnp.int32)
    seg = jnp.array([0, 0, 0], jnp.int32)
    m, idx = segment_argmax_tie(vals, tie, seg, 1)
    assert int(idx[0]) == 1  # max value, smallest tie key


def test_partition_2d_roundtrip():
    n = 50
    rr, cc, vv = _rand_coo(n, 300, seed=7)
    part = partition_coo_2d(rr, cc, vv, n, 4, 2)
    got = set()
    for a in range(4):
        for b in range(2):
            k = int(part.nnz[a, b])
            for t in range(k):
                i, j, w = int(part.row[a, b, t]), int(part.col[a, b, t]), float(part.val[a, b, t])
                assert i // part.br == a and j // part.bc == b
                got.add((i, j, np.float32(w)))
    expect = set(zip(rr.tolist(), cc.tolist(), vv.tolist()))
    assert got == expect
    # per-block lex sort
    for a in range(4):
        for b in range(2):
            k = int(part.nnz[a, b])
            pairs = list(zip(part.row[a, b, :k].tolist(), part.col[a, b, :k].tolist()))
            assert pairs == sorted(pairs)


def _lexsort_order(rr, cc, n, pr, pc):
    """The plain reference order: ``np.lexsort((col, row, block))`` and the
    block of each edge."""
    blk = (rr // -(-n // pr)) * pc + cc // -(-n // pc)
    order = np.lexsort((cc, rr, blk))
    return order, blk[order]


def _with_duplicates(n, m, seed):
    """A lex-sorted instance in which some (row, col) pairs repeat with other
    values: the order keeps the input order among equal pairs."""
    rr, cc, vv = _rand_coo(n, m, seed)
    rep = np.arange(0, rr.size, 3)
    rr, cc = np.concatenate([rr, rr[rep]]), np.concatenate([cc, cc[rep]])
    vv = np.concatenate([vv, vv[rep] + 1])
    order = np.lexsort((cc, rr))  # stable: each original before its copy
    return rr[order], cc[order], vv[order]


@pytest.mark.parametrize("order", ["sorted", "shuffled"])
@pytest.mark.parametrize("grid", [(1, 1), (2, 2), (2, 4), (4, 2)],
                         ids=lambda g: f"{g[0]}x{g[1]}")
@pytest.mark.parametrize("instance", ["distinct", "duplicates"])
def test_partition_2d_equals_the_lexsort_reference(instance, grid, order):
    """Every block holds its edges in the plain three-key lexsort's order,
    for lex-sorted input (the linear pass) and for a shuffled copy (the
    lexsort fallback); counts, capacity and padding as before."""
    n, (pr, pc) = 50, grid
    if instance == "distinct":
        rr, cc, vv = _rand_coo(n, 300, seed=7)
    else:
        rr, cc, vv = _with_duplicates(n, 300, seed=7)
    if order == "shuffled":
        perm = np.random.default_rng(3).permutation(rr.size)
        rr, cc, vv = rr[perm], cc[perm], vv[perm]
    part = partition_coo_2d(rr, cc, vv, n, pr, pc)
    ref, blk = _lexsort_order(rr, cc, n, pr, pc)
    counts = np.bincount(blk, minlength=pr * pc)
    np.testing.assert_array_equal(part.nnz.reshape(-1), counts)
    assert part.cap == max(-(-int(counts.max()) // 8) * 8, 8)
    for p in range(pr * pc):
        k, sel = counts[p], ref[blk == p]
        a, b = divmod(p, pc)
        np.testing.assert_array_equal(part.row[a, b, :k], rr[sel])
        np.testing.assert_array_equal(part.col[a, b, :k], cc[sel])
        np.testing.assert_array_equal(part.val[a, b, :k].view(np.uint32),
                                      vv[sel].view(np.uint32))
        assert (part.row[a, b, k:] == n).all()
        assert (part.col[a, b, k:] == n).all()
        assert (part.val[a, b, k:] == 0).all()


def test_padded_csr():
    rr, cc, vv = _rand_coo(11, 40, seed=9)
    csr = coo_to_padded_csr(rr, cc, vv, 11, 11, capacity=64)
    assert csr.capacity == 64
    assert csr.row_ptr[-1] == csr.nnz
    for i in range(11):
        s, e = csr.row_ptr[i], csr.row_ptr[i + 1]
        assert (csr.row[s:e] == i).all()
        assert (np.diff(csr.col[s:e]) > 0).all()
