"""Plain numpy reference of the matching engine's rules: greedy maximal
matching in proposal rounds, maximum cardinality matching by layered BFS
with a lockstep trace and flip, then AWAC rounds of augmenting 4-cycles.

It follows the published algorithm (arXiv:1801.09809, Algorithms 2-6) with
the engine's documented tie-breaks, so that the same instance gives the same
mates, the same number of AWAC rounds and the same weight bits:

- every "pick the heaviest" takes the largest value and, among equal values,
  the smallest index (edge position in the lex-sorted list, column or row
  id, as the rule says);
- a 4-cycle's gain is ``((w1 + w2) - u_i) - v_j``, rounded in the working
  precision after each operation;
- the weight is summed in one fixed pairwise order.

It imports nothing of the program. ``dtype`` is the working precision:
float32 as the configuration states, or a lower one for the control.
Edges are sparse throughout: O(m) arrays per round, never an n x n one.
"""
from __future__ import annotations

import dataclasses

import numpy as np

MIN_GAIN = 1e-6  # the paper's epsilon: a 4-cycle must gain more than this


@dataclasses.dataclass
class Matching:
    mate_row: np.ndarray  # [n + 1] int: row matched to column j (n = none)
    mate_col: np.ndarray  # [n + 1] int: column matched to row i
    awac_rounds: int
    weight: np.generic  # sum of matched weights in the working precision


def _best_per_segment(values, payload, seg, n_seg):
    """Per segment: the largest value and, among equals, the smallest
    payload. Empty segments get (-inf, -1)."""
    best_v = np.full(n_seg, -np.inf, np.float64)
    best_p = np.full(n_seg, -1, np.int64)
    if seg.size:
        order = np.lexsort((payload, -values.astype(np.float64), seg))
        s = seg[order]
        first = order[np.r_[True, s[1:] != s[:-1]]]
        best_v[seg[first]] = values[first]
        best_p[seg[first]] = payload[first]
    return best_v, best_p


def greedy(row, col, val, n):
    """Weighted greedy maximal matching in proposal rounds: each free column
    proposes its heaviest edge to a free row; each row accepts its heaviest
    proposal."""
    mate_row = np.full(n + 1, n, np.int64)
    mate_col = np.full(n + 1, n, np.int64)
    eidx = np.arange(row.size)
    while True:
        e = eidx[(mate_col[row] == n) & (mate_row[col] == n)]
        pv, pe = _best_per_segment(val[e], e, col[e], n)
        j = np.flatnonzero(pe >= 0)
        _, rj = _best_per_segment(pv[j], j, row[pe[j]], n)
        ok = np.flatnonzero(rj >= 0)
        if ok.size == 0:
            return mate_row, mate_col
        mate_col[ok] = rj[ok]
        mate_row[rj[ok]] = ok


def _bfs(row, col, val, n, mate_row, mate_col):
    """One layered BFS from every free column; a row reached in a layer
    takes its heaviest eligible edge's column as parent."""
    eidx = np.arange(row.size)
    frontier = np.zeros(n + 1, bool)
    frontier[:n] = mate_row[:n] == n
    parent_col = np.full(n + 1, n, np.int64)
    visited = np.zeros(n + 1, bool)
    found, layers, progressed = False, 0, True
    while not found and progressed and layers <= n:
        e = eidx[frontier[col] & ~visited[row]]
        _, re = _best_per_segment(val[e], e, row[e], n)
        new = np.flatnonzero(re >= 0)
        parent_col[new] = col[re[new]]
        visited[new] = True
        free = mate_col[new] == n
        found = bool(free.any())
        frontier = np.zeros(n + 1, bool)
        frontier[mate_col[new[~free]]] = True
        frontier[n] = False
        layers += 1
        progressed = new.size > 0
    return parent_col, visited, found, layers


def _trace_and_flip(parent_col, visited, found, layers, mate_row, mate_col,
                    n):
    """Walk back from every free row the BFS reached, one column per step;
    where walkers meet at a column the smallest row id keeps it. Then flip
    the paths that survived all steps."""
    widx = np.arange(n + 1)
    active = np.zeros(n + 1, bool)
    if found:
        active[:n] = visited[:n] & (mate_col[:n] == n)
    cur = widx.copy()
    big = np.iinfo(np.int64).max
    for _ in range(layers):
        j_w = np.where(active, parent_col[cur], n)
        win = np.full(n + 1, big, np.int64)
        np.minimum.at(win, j_w, widx)
        active &= win[j_w] == widx
        nxt = mate_row[j_w]
        cur = np.where(active & (nxt < n), nxt, cur)
    surv, cur = active, widx.copy()
    for _ in range(layers):
        j = np.where(surv, parent_col[cur], n)
        prev = mate_row[j]
        s = np.flatnonzero(surv)
        mate_row[j[s]] = cur[s]
        mate_col[cur[s]] = j[s]
        mate_row[n] = n
        mate_col[n] = n
        surv = surv & (prev < n)
        cur = np.where(surv, prev, cur)
    return mate_row, mate_col


def mcm(row, col, val, n, mate_row, mate_col):
    """Maximum cardinality matching: BFS phases until no augmenting path."""
    while (mate_row[:n] == n).any():
        parent_col, visited, found, layers = _bfs(row, col, val, n, mate_row,
                                                  mate_col)
        mate_row, mate_col = _trace_and_flip(parent_col, visited, found,
                                             layers, mate_row, mate_col, n)
        if not found:
            break
    return mate_row, mate_col


def _edge_lookup(key, q):
    pos = np.searchsorted(key, q)
    pos_c = np.minimum(pos, key.size - 1)
    return pos_c, (pos < key.size) & (key[pos_c] == q)


def _matched_weights(key, val, n, mate_row, mate_col, dtype):
    """u[i]: weight of row i's matched edge; v[j]: of column j's."""
    u = np.zeros(n + 1, dtype)
    v = np.zeros(n + 1, dtype)
    i = np.arange(n)
    pos, found = _edge_lookup(key, i * (n + 1) + mate_col[:n])
    u[:n] = np.where(found & (mate_col[:n] < n), val[pos], 0)
    v[:n] = np.where(mate_row[:n] < n, u[np.minimum(mate_row[:n], n)], 0)
    return u, v


def awac_round(row, col, val, key, n, mate_row, mate_col, u, v, min_gain):
    """One AWAC round. Step A/B: for each edge (i, j) the 4-cycle through
    m_j and m_i and its gain; step C: each column's best cycle; step D: a
    cycle survives when it is the best of those that share its row's matched
    column, which is itself not rooted; then every survivor is rotated.
    With no survivor, the single best cycle is. Returns the survivors."""
    qr = mate_row[col]
    qc = mate_col[row]
    pos, found = _edge_lookup(key, qr * (n + 1) + qc)
    found &= qr < n
    w2 = np.where(found, val[pos], val.dtype.type(0))
    gain = ((val + w2) - u[row]) - v[col]
    e = np.flatnonzero(found & (row > qr) & (gain > min_gain))
    c_gain, c_edge = _best_per_segment(gain[e], e, col[e], n)
    rooted = c_edge >= 0
    jr = np.flatnonzero(rooted)
    ci = row[c_edge[jr]]
    _, dj = _best_per_segment(c_gain[jr], jr, mate_col[ci], n + 1)
    c2 = np.flatnonzero((dj[:n] >= 0) & ~rooted)
    mask = np.zeros(n, bool)
    mask[dj[c2]] = True
    mask &= rooted
    if not mask.any() and rooted.any():
        mask[np.argmax(np.where(rooted, c_gain, -np.inf))] = True
    js = np.flatnonzero(mask)
    es = c_edge[js]
    i_ = row[es]
    w1, w2s = val[es], w2[es]
    r2 = mate_row[js]
    c2 = mate_col[i_]
    mate_row[js] = i_
    mate_row[c2] = r2
    mate_col[i_] = js
    mate_col[r2] = c2
    u[i_] = w1
    u[r2] = w2s
    v[js] = w1
    v[c2] = w2s
    for a in (mate_row, mate_col):
        a[n] = n
    u[n] = v[n] = 0
    return js.size


def ordered_sum(x):
    """Sum in one fixed pairwise order: adjacent pairs, level by level."""
    size = 1 << max(x.size - 1, 0).bit_length()
    x = np.concatenate([x, np.zeros(size - x.size, x.dtype)])
    while x.size > 1:
        x = x[0::2] + x[1::2]
    return x[0]


def repair(key, n, mate_row, mate_col):
    """A warm start's seed against this instance: a pair (i, j) survives
    when it is mutual and its edge exists; every other entry is unmatched."""
    mr = np.asarray(mate_row, np.int64)[:n]
    mc = np.asarray(mate_col, np.int64)[:n]
    j = np.arange(n)
    i = np.where((mr >= 0) & (mr < n), mr, n)
    _, exists = _edge_lookup(key, i * (n + 1) + j)
    keep = (i < n) & (mc[np.minimum(i, n - 1)] == j) & exists
    new_row = np.full(n + 1, n, np.int64)
    new_col = np.full(n + 1, n, np.int64)
    new_row[j[keep]] = i[keep]
    new_col[i[keep]] = j[keep]
    return new_row, new_col


def solve(row, col, val, n, dtype=np.float32, max_rounds: int = 1000,
          min_gain: float = MIN_GAIN, warm=None) -> Matching:
    """Greedy -> MCM -> AWAC on the padded, lex-sorted COO ``row, col, val``
    (padding rows are n), computed in ``dtype``. With ``warm``, a previous
    matching's (mate_row, mate_col), the repaired seed takes greedy's place
    and MCM tops it up."""
    real = row < n
    row = row[real].astype(np.int64)
    col = col[real].astype(np.int64)
    val = val[real].astype(dtype)
    key = row * (n + 1) + col
    if warm is None:
        mate_row, mate_col = greedy(row, col, val, n)
    else:
        mate_row, mate_col = repair(key, n, *warm)
    mate_row, mate_col = mcm(row, col, val, n, mate_row, mate_col)
    u, v = _matched_weights(key, val, n, mate_row, mate_col, dtype)
    gate = dtype(min_gain)
    rounds = 0
    go = bool((mate_row[:n] < n).all())
    while go and rounds < max_rounds:
        go = awac_round(row, col, val, key, n, mate_row, mate_col, u, v,
                        gate) > 0
        rounds += 1
    return Matching(mate_row, mate_col, rounds, ordered_sum(u[:n]))
