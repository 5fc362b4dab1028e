"""The sorted-segment reduction and the MCM that runs on it.

Contract under test: ``sorted_segment_max_with_payload`` over contiguous
runs gives what the two-pass ``segment_max_with_payload`` gives, value and
payload bit for bit, whenever its levels cover the longest run; and
``single.mcm``, whose BFS picks each row's parent with it, gives the numpy
reference's mates and the batched engine's, with the level count measured
from the rows or, under an outer ``jit``, taken from the shape bound.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference
from repro.core import batch, graph, single
from repro.sparse.csr import row_ptr_from_sorted
from repro.sparse.ops import (
    batched_segment_argmax_tie,
    batched_sorted_segment_max_with_payload,
    segment_max_with_payload,
    sorted_segment_max_with_payload,
)


def _runs(lengths, values, payload=None, pad=0):
    """Segment ids for rows of ``lengths`` in order, ``pad`` padding entries
    (id n) after them; (values, payload, ids, row_ptr, n) as device
    arrays, ``payload`` the entry index unless given."""
    n = len(lengths)
    ids = np.concatenate([np.repeat(np.arange(n), lengths),
                          np.full(pad, n)]).astype(np.int32)
    m = ids.size
    values = np.concatenate([np.asarray(values, np.float32),
                             np.zeros(pad, np.float32)])
    assert values.size == m
    payload = np.arange(m) if payload is None else np.concatenate(
        [payload, np.arange(pad) + 10_000])
    ids = jnp.asarray(ids)
    return (jnp.asarray(values), jnp.asarray(payload, jnp.int32), ids,
            row_ptr_from_sorted(ids, n)[:n + 1], n)


def _levels(lengths):
    return math.ceil(math.log2(max(max(lengths), 1)))


def _case(name):
    rng = np.random.default_rng(7)
    if name == "value ties, other payloads":
        lengths = rng.integers(1, 12, 40)
        m = int(lengths.sum())
        values = rng.integers(0, 3, m) / 2  # few distinct values
        payload = rng.permutation(m) * 3
        return lengths, values, payload, 0
    if name == "rows all -inf":
        lengths = [3, 5, 1, 4]
        values = np.r_[[-np.inf] * 3, 0.5, -np.inf, 0.5, -np.inf, -np.inf,
                       [-np.inf], [-np.inf] * 4]
        return lengths, values, None, 0
    if name == "empty rows":
        lengths = [0, 4, 0, 0, 2, 0]
        return lengths, rng.random(6), None, 0
    if name == "degree 1":
        lengths = [1] * 9
        return lengths, rng.random(9), None, 0
    if name == "a row of 2^L":
        lengths = [3, 16, 5]  # L = 4 covers it exactly
        values = rng.random(24)
        values[3] = 2.0  # the row's max on its first entry
        return lengths, values, None, 0
    if name == "a row of 2^L + 1":
        lengths = [3, 17, 5]  # L = 5
        values = rng.random(25)
        values[3] = 2.0
        return lengths, values, None, 0
    if name == "padding tail":
        lengths = [2, 7, 3]
        values = rng.random(12)
        return lengths, values, None, 9
    if name == "+0 and -0 tie":
        lengths = [4, 3, 2]
        values = [0.0, -0.0, 0.0, -0.0, -0.0, 0.0, -0.0, -0.0, -0.0]
        payload = np.array([5, 2, 9, 7, 6, 4, 1, 3, 8])
        return lengths, np.array(values), payload, 0
    raise ValueError(name)


CASES = ["value ties, other payloads", "rows all -inf", "empty rows",
         "degree 1", "a row of 2^L", "a row of 2^L + 1", "padding tail",
         "+0 and -0 tie"]


@pytest.mark.parametrize("name", CASES)
def test_sorted_segment_matches_two_pass(name):
    lengths, values, payload, pad = _case(name)
    v, p, ids, ptr, n = _runs(lengths, values, payload, pad)
    levels = _levels(lengths)
    want_v, want_p = segment_max_with_payload(v, p, ids, n + 1)
    got_v, got_p = jax.jit(sorted_segment_max_with_payload,
                           static_argnames="levels")(v, p, ids, ptr, levels)
    np.testing.assert_array_equal(
        np.asarray(got_v).view(np.uint32), np.asarray(want_v)[:n].view(
            np.uint32))
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p)[:n])
    if name == "a row of 2^L + 1":
        # one level short, the row's last entry never sees its first
        _, short_p = sorted_segment_max_with_payload(v, p, ids, ptr,
                                                     levels - 1)
        assert int(short_p[1]) != int(want_p[1]) == 3


def _block(lengths, rng, r0, bc, tie_values=False):
    """One instance's edge block as the grid partition leaves it: rows
    ``r0, r0 + 1, ...`` of ``lengths`` entries, each row's columns distinct
    and sorted, in ``[0, bc)``; (row, col, val) numpy arrays."""
    rows, cols = [], []
    for k, length in enumerate(lengths):
        rows.append(np.full(length, r0 + k))
        cols.append(np.sort(rng.choice(bc, length, replace=False)))
    m = int(sum(lengths))
    val = rng.integers(0, 3, m) / 2 if tie_values else rng.random(m)
    return (np.concatenate(rows + [np.zeros(0)]).astype(np.int32),
            np.concatenate(cols + [np.zeros(0)]).astype(np.int32),
            val.astype(np.float32))


def _block_case(name):
    """Per-instance row lengths, whether values tie, and the rows whose
    values are all -inf."""
    rng = np.random.default_rng(11)
    if name == "value ties, other columns":
        return [list(rng.integers(1, 12, 30))], True, ()
    if name == "rows all -inf":
        return [[3, 5, 1, 4, 6]], False, (0, 2, 4)
    if name == "empty rows and blocks":
        return [[0, 4, 0, 0, 2, 0], [0] * 6, [1, 0, 3, 0, 0, 2]], False, ()
    if name == "a row of 2^L":
        return [[3, 16, 5]], False, ()
    if name == "a row of 2^L + 1":
        return [[3, 17, 5]], False, ()
    if name == "instances of other sizes":
        return [list(rng.integers(0, 9, 20)), list(rng.integers(5, 33, 20)),
                [1] * 20], True, (3, 7)
    raise ValueError(name)


BLOCK_CASES = ["value ties, other columns", "rows all -inf",
               "empty rows and blocks", "a row of 2^L", "a row of 2^L + 1",
               "instances of other sizes"]


@pytest.mark.parametrize("x64", [False, True], ids=["f32", "x64"])
@pytest.mark.parametrize("name", BLOCK_CASES)
def test_batched_scan_over_blocks_matches_the_scatter(name, x64):
    """The grid's parent pick: the batched scan over [B, cap] row-sorted
    blocks with a padding tail (rows == n) gives
    ``batched_segment_argmax_tie``'s value bits, and the column of its
    edge, with and without the packed 64-bit keys."""
    per_instance, ties, dead = _block_case(name)
    rng = np.random.default_rng(5)
    n, bc, r0 = 200, 40, 64  # this block's rows are [r0, r0 + k)
    k = len(per_instance[0])
    blocks = [_block(lengths, rng, r0, bc, ties) for lengths in per_instance]
    cap = max(r.size for r, _, _ in blocks) + 5  # a padding tail in each
    b = len(blocks)
    row = np.full((b, cap), n, np.int32)
    col = np.full((b, cap), n, np.int32)
    val = np.zeros((b, cap), np.float32)
    for i, (r, c, v) in enumerate(blocks):
        row[i, :r.size], col[i, :r.size], val[i, :r.size] = r, c, v
    score = np.where(row < n, val, -np.inf).astype(np.float32)
    score[np.isin(row, [r0 + d for d in dead])] = -np.inf
    ptr = np.stack([np.searchsorted(r, r0 + np.arange(k + 1))
                    for r in row]).astype(np.int32)
    levels = _levels([length for lengths in per_instance
                      for length in lengths])
    li = np.where(row < n, row - r0, k).astype(np.int32)
    with jax.enable_x64(x64):
        want_v, want_i = batched_segment_argmax_tie(
            jnp.asarray(score), jnp.asarray(col), jnp.asarray(li), k + 1)
        got_v, got_c = jax.jit(batched_sorted_segment_max_with_payload,
                               static_argnames="levels")(
            jnp.asarray(score), jnp.asarray(col), jnp.asarray(row),
            jnp.asarray(ptr), levels)
    want_v, want_i = np.asarray(want_v)[:, :k], np.asarray(want_i)[:, :k]
    want_c = np.where(want_i >= 0, np.take_along_axis(
        col, np.clip(want_i, 0, None), axis=1), -1)
    np.testing.assert_array_equal(np.asarray(got_v).view(np.uint32),
                                  want_v.view(np.uint32))
    np.testing.assert_array_equal(np.asarray(got_c), want_c)
    assert (want_c >= 0).any()
    assert (want_c < 0).any() == bool(dead or name.startswith("empty"))


def _skewed(n=1200, seed=3):
    """A uniform degree-4 instance with a planted perfect matching, plus
    row 5 joined to every column: one row of degree n."""
    g = graph.generate(n, avg_degree=4.0, kind="uniform", seed=seed)
    real = g.row < n
    rng = np.random.default_rng(seed)
    row = np.r_[g.row[real], np.full(n, 5)]
    col = np.r_[g.col[real], np.arange(n)]
    val = np.r_[g.val[real], rng.uniform(0.01, 1.0, n)]
    _, first = np.unique(row.astype(np.int64) * n + col, return_index=True)
    g = graph.from_coo(row[first], col[first], val[first], n)
    assert np.bincount(g.row[g.row < n]).max() == n
    return g


def _reference_mcm(g, mate_row, mate_col):
    real = g.row < g.n
    mr, mc = reference.mcm(g.row[real].astype(np.int64),
                           g.col[real].astype(np.int64), g.val[real], g.n,
                           np.asarray(mate_row, np.int64).copy(),
                           np.asarray(mate_col, np.int64).copy())
    return mr, mc


@pytest.mark.parametrize("start", ["empty", "greedy"])
def test_mcm_adapts_its_levels_to_a_dense_row(start):
    g = _skewed()
    n = g.n
    row, col, val = (jnp.asarray(x) for x in (g.row, g.col, g.val))
    ws = single._resolve_window_steps(row, n, None)
    assert single._scan_levels(row, n, ws) == math.ceil(math.log2(n))
    st0 = single.empty_state(n) if start == "empty" else \
        single.greedy_maximal(row, col, val, n)
    st = single.mcm(row, col, val, n, st0.mate_row, st0.mate_col)
    want_r, want_c = _reference_mcm(g, st0.mate_row, st0.mate_col)
    np.testing.assert_array_equal(np.asarray(st.mate_row), want_r)
    np.testing.assert_array_equal(np.asarray(st.mate_col), want_c)
    bmr, bmc = batch.mcm_batched(row[None], col[None], val[None], n,
                                 st0.mate_row[None], st0.mate_col[None])
    np.testing.assert_array_equal(np.asarray(st.mate_row), np.asarray(bmr[0]))
    np.testing.assert_array_equal(np.asarray(st.mate_col), np.asarray(bmc[0]))


@pytest.mark.parametrize("kind", ["skewed", "antigreedy"])
def test_mcm_inside_an_outer_jit(kind):
    """Under a trace the rows cannot be measured: the levels cover a row
    of min(cap, n) entries, and the mates stay the eager ones."""
    g = _skewed(n=600) if kind == "skewed" else graph.generate(
        256, avg_degree=5.0, kind="antigreedy", seed=2)
    n = g.n
    row, col, val = (jnp.asarray(x) for x in (g.row, g.col, g.val))
    st0 = single.greedy_maximal(row, col, val, n)
    traced = jax.jit(lambda r, c, v, mr, mc: single.mcm(r, c, v, n, mr, mc))(
        row, col, val, st0.mate_row, st0.mate_col)
    eager = single.mcm(row, col, val, n, st0.mate_row, st0.mate_col)
    want_r, want_c = _reference_mcm(g, st0.mate_row, st0.mate_col)
    for got in (traced, eager):
        np.testing.assert_array_equal(np.asarray(got.mate_row), want_r)
        np.testing.assert_array_equal(np.asarray(got.mate_col), want_c)
    for a, b in zip(traced, eager):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    levels = []

    def traced_levels(r):
        levels.append(single._scan_levels(
            r, n, single._resolve_window_steps(r, n, None)))
        return r

    jax.jit(traced_levels)(row)
    assert levels == [math.ceil(math.log2(min(g.capacity, n)))]
