"""Single-device (pure jnp, jit-able) AWPM: greedy maximal -> MCM -> AWAC.

This is both (a) the single-node baseline the paper compares against ("sequential
AWPM", §6.1) and (b) the reference implementation the distributed shard_map
version must agree with: the Step C/D selection + augmentation logic
(`select_and_augment`) is *shared* between the two — the distributed code only
replaces how the per-column Step-C winners are computed (local segment ops +
collectives instead of full-array segment ops).

Conventions (everywhere in repro.core):
  - square matrix, n rows == n cols; edges as padded COO sorted lex by (row, col)
    with padding entries (n, n, 0).
  - ``mate_row`` [n+1]: row matched to column j (sentinel n = unmatched;
    slot n is always n). ``mate_col`` [n+1]: column matched to row i.
  - ``u`` [n+1]: weight of row i's matched edge; ``v`` [n+1]: weight of column
    j's matched edge. Slot n is 0.
  - all weights float32; gains computed as ``w1 + w2 - u - v`` in that order so
    numpy reference and jnp agree exactly.
"""
from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import telemetry
from repro.core._compat import warn_legacy
from repro.core.constants import MIN_GAIN
from repro.sparse.csr import max_row_nnz, row_ptr_from_sorted, window_depth
from repro.sparse.ops import (
    lex_searchsorted,
    searchsorted_in_window,
    segment_max_with_payload,
    sorted_segment_max_with_payload,
)

NEG = -jnp.inf

# Fallback windowed-search depth when the row array is a tracer and the max
# row degree cannot be measured on the host (covers any int32-sized window).
FALLBACK_WINDOW_STEPS = 32


class MatchState(NamedTuple):
    mate_row: jnp.ndarray  # [n+1] int32
    mate_col: jnp.ndarray  # [n+1] int32
    u: jnp.ndarray  # [n+1] float32
    v: jnp.ndarray  # [n+1] float32


def empty_state(n: int) -> MatchState:
    return MatchState(
        jnp.full((n + 1,), n, jnp.int32),
        jnp.full((n + 1,), n, jnp.int32),
        jnp.zeros((n + 1,), jnp.float32),
        jnp.zeros((n + 1,), jnp.float32),
    )


def state_from_mates(row, col, val, n, mate_row, mate_col) -> MatchState:
    """Build MatchState (incl. u, v) from mate arrays (numpy or jnp, len n or n+1)."""
    mate_row = jnp.asarray(mate_row, jnp.int32)
    mate_col = jnp.asarray(mate_col, jnp.int32)
    if mate_row.shape[0] == n:
        mate_row = jnp.concatenate([mate_row, jnp.array([n], jnp.int32)])
        mate_col = jnp.concatenate([mate_col, jnp.array([n], jnp.int32)])
    ivec = jnp.arange(n, dtype=jnp.int32)
    pos, found = lex_searchsorted(row, col, ivec, mate_col[:n])
    uu = jnp.where(found, val[pos], 0.0)
    u = jnp.zeros((n + 1,), jnp.float32).at[:n].set(uu)
    v = jnp.zeros((n + 1,), jnp.float32).at[:n].set(
        jnp.where(mate_row[:n] < n, u[mate_row[:n]], 0.0)
    )
    return MatchState(mate_row, mate_col, u, v)


def ordered_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Sum over the last axis in one fixed pairwise order.

    ``jnp.sum`` leaves the order of the additions to the compiler, and the
    TPU's order differs from the CPU's, so the same f32 values can sum to
    different last bits. Here each level adds adjacent pairs elementwise,
    which every platform rounds alike: the weight is bit-identical across
    platforms, batch shapes and device grids."""
    m = x.shape[-1]
    size = 1 << max(m - 1, 0).bit_length()
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, size - m)])
    while x.shape[-1] > 1:
        x = x[..., 0::2] + x[..., 1::2]
    return x[..., 0]


def matching_weight(state: MatchState, n: int) -> jnp.ndarray:
    return ordered_sum(state.u[:n])


def is_perfect(state: MatchState, n: int) -> jnp.ndarray:
    return (state.mate_row[:n] < n).all()


def _jit_named(name: str, fn, **jit_kwargs):
    """``jax.jit(fn)`` compiled as the module ``jit_<name>``. Each counted
    engine core below returns its counters beside the phase's result and
    compiles under the public phase's name, the name a device trace finds
    the phase by; the public function is a plain wrapper over it."""

    @functools.wraps(fn)
    def named(*args, **kwargs):
        return fn(*args, **kwargs)

    named.__name__ = named.__qualname__ = name
    return jax.jit(named, **jit_kwargs)


# --------------------------------------------------------------------------
# Phase 1: greedy weighted maximal matching (proposal rounds)
# --------------------------------------------------------------------------


@jax.named_scope("greedy_round")
def greedy_round(row, col, val, n: int, mate_row, mate_col):
    """One proposal round of the greedy weighted maximal matching. The
    batched engine (core/batch.py) re-expresses this body on flat
    offset-segment primitives — any change here must be mirrored in
    ``batch._greedy_maximal_batched`` to keep per-instance bit-exactness.
    Returns (mate_row, mate_col, progressed)."""
    cap = row.shape[0]
    eidx = jnp.arange(cap, dtype=jnp.int32)
    jvec = jnp.arange(n, dtype=jnp.int32)
    ivec = jnp.arange(n, dtype=jnp.int32)
    avail = (row < n) & (mate_col[row] == n) & (mate_row[col] == n)
    score = jnp.where(avail, val, NEG)
    seg = jnp.where(avail, col, n)
    pg, pe = segment_max_with_payload(score, eidx, seg, n + 1)
    has = pe[:n] >= 0
    prow = jnp.where(has, row[jnp.clip(pe[:n], 0)], n)
    pv = jnp.where(has, pg[:n], NEG)
    _, rj = segment_max_with_payload(pv, jvec, prow, n + 1)
    ok = rj[:n] >= 0  # per-row winning proposal col
    wcol = jnp.where(ok, rj[:n], n).astype(jnp.int32)
    mate_col = mate_col.at[jnp.where(ok, ivec, n)].set(wcol)
    mate_row = mate_row.at[wcol].set(jnp.where(ok, ivec, n).astype(jnp.int32))
    mate_col = mate_col.at[n].set(n)
    mate_row = mate_row.at[n].set(n)
    return mate_row, mate_col, ok.any()


def _greedy_rounds(row, col, val, n: int):
    """Proposal rounds until one matches nothing. Returns (MatchState,
    counters): the rounds run and the pairs matched (``telemetry``)."""

    def round_body(carry):
        mate_row, mate_col, _, rounds = carry
        return (*greedy_round(row, col, val, n, mate_row, mate_col),
                rounds + 1)

    def cond(carry):
        return carry[2]

    st0 = empty_state(n)
    mate_row, mate_col, _, rounds = jax.lax.while_loop(
        cond, round_body, (st0.mate_row, st0.mate_col, jnp.array(True),
                           jnp.array(0, jnp.int32))
    )
    # pairs counted on the rows' side: a count of ``mate_row`` makes the TPU
    # compiler place the loop's mate arrays differently (a slower round)
    counters = {"greedy_rounds": rounds,
                "greedy_matched": (mate_col[:n] < n).sum(dtype=jnp.int32)}
    return state_from_mates(row, col, val, n, mate_row, mate_col), counters


_greedy_counted = _jit_named("greedy_maximal", _greedy_rounds,
                             static_argnames=("n",))


def greedy_maximal(row, col, val, n: int) -> MatchState:
    return _greedy_counted(row, col, val, n)[0]


# --------------------------------------------------------------------------
# Phase 2: maximum cardinality matching (layered BFS + lockstep trace/flip)
# --------------------------------------------------------------------------


@jax.named_scope("mcm_trace_flip")
def trace_and_flip(parent_col, visited, found, layers, mate_row, mate_col, n):
    """Lockstep backtrace with per-column claims (winner = smallest endpoint
    row id), then flip the surviving vertex-disjoint augmenting paths.

    All augmenting paths from one layered BFS have the same number of column
    steps (``layers``) and every column belongs to exactly one BFS layer, so
    claim conflicts can only occur between walkers at the same step — one
    claim round per step suffices. Shared verbatim by the distributed MCM.
    """
    widx = jnp.arange(n + 1, dtype=jnp.int32)  # walker ids (= endpoint row ids)
    endpoints = jnp.zeros((n + 1,), bool).at[:n].set(
        visited[:n] & (mate_col[:n] == n)
    ) & found

    def claim_body(carry):
        active, cur, t = carry
        j_w = jnp.where(active, parent_col[cur], n)
        win = jax.ops.segment_min(widx, j_w, num_segments=n + 1)
        active = active & (win[j_w] == widx)
        nxt = mate_row[j_w]
        cur = jnp.where(active & (nxt < n), nxt, cur)
        return active, cur, t + 1

    active, _, _ = jax.lax.while_loop(
        lambda c: c[2] < layers,
        claim_body,
        (endpoints, widx, jnp.array(0, jnp.int32)),
    )

    def flip_body(carry):
        surv, cur, mate_row, mate_col, t = carry
        j = jnp.where(surv, parent_col[cur], n)
        prev = mate_row[j]
        mate_row = mate_row.at[j].set(jnp.where(surv, cur, mate_row[j]).astype(jnp.int32))
        mate_col = mate_col.at[jnp.where(surv, cur, n)].set(j.astype(jnp.int32))
        mate_row = mate_row.at[n].set(n)
        mate_col = mate_col.at[n].set(n)
        surv = surv & (prev < n)
        cur = jnp.where(surv, prev, cur)
        return surv, cur, mate_row, mate_col, t + 1

    _, _, mate_row, mate_col, _ = jax.lax.while_loop(
        lambda c: c[4] < layers,
        flip_body,
        (active, widx, mate_row, mate_col, jnp.array(0, jnp.int32)),
    )
    return mate_row, mate_col


def _mcm_bfs(row, col, val, row_ptr, n: int, mate_row, mate_col,
             levels: int):
    """One layered BFS from all free rows with weight-aware parent selection.
    Each row's parent is its heaviest edge into the frontier, reduced over
    the row's contiguous run of edges (``sorted_segment_max_with_payload``,
    ``levels`` covering the longest row); a row already visited keeps its
    parent. Within a row the edges are sorted by column, so the smallest
    column among the heaviest is the edge of smallest index, the tie-break
    of every other reduction: the reduction carries the column itself.
    Returns (parent_col, visited, found, layers)."""
    frontier = jnp.zeros((n + 1,), bool).at[:n].set(mate_row[:n] == n)
    parent_col = jnp.full((n + 1,), n, jnp.int32)
    visited = jnp.zeros((n + 1,), bool)

    def bfs_body(carry):
        frontier, parent_col, visited, found, layers, _ = carry
        # padding edges lie past row_ptr[n], in no row that is read
        score = jnp.where(frontier[col], val, NEG)
        _, best = sorted_segment_max_with_payload(score, col, row,
                                                  row_ptr[:n + 1], levels)
        new = (best >= 0) & ~visited[:n]
        parent_col = parent_col.at[:n].set(
            jnp.where(new, best, parent_col[:n]))
        visited = visited.at[:n].set(visited[:n] | new)
        free_new = new & (mate_col[:n] == n)
        found = free_new.any()
        nf_idx = jnp.where(new & ~free_new, mate_col[:n], n)
        frontier = jnp.zeros((n + 1,), bool).at[nf_idx].set(True).at[n].set(False)
        return frontier, parent_col, visited, found, layers + 1, new.any()

    def bfs_cond(carry):
        _, _, _, found, layers, progressed = carry
        return (~found) & progressed & (layers <= n)

    with jax.named_scope("mcm_bfs_layer"):
        frontier, parent_col, visited, found, layers, _ = jax.lax.while_loop(
            bfs_cond,
            bfs_body,
            (frontier, parent_col, visited, jnp.array(False),
             jnp.array(0, jnp.int32), jnp.array(True)),
        )
    return parent_col, visited, found, layers


def mcm_phase(row, col, val, row_ptr, n: int, mate_row, mate_col,
              levels: int):
    """One MCM phase: layered BFS + trace/flip of the augmenting paths it
    found. The batched engine re-expresses this phase on flat
    offset-segment primitives (``batch._mcm_bfs_batched`` /
    ``batch.trace_and_flip_batched``) — changes here must be mirrored there
    to keep per-instance bit-exactness. Returns (mate_row, mate_col,
    found, layers), ``layers`` the BFS's."""
    parent_col, visited, found, layers = _mcm_bfs(
        row, col, val, row_ptr, n, mate_row, mate_col, levels)
    mate_row, mate_col = trace_and_flip(
        parent_col, visited, found, layers, mate_row, mate_col, n
    )
    return mate_row, mate_col, found, layers


def _mcm_phases(row, col, val, row_ptr, n: int, mate_row, mate_col,
                levels: int):
    """MCM phases until one finds no augmenting path or every row is
    matched. Returns (MatchState, counters): the BFS layers of all phases,
    each of which ran the sorted-segment reduction (``telemetry``)."""

    def phase_body(carry):
        mate_row, mate_col, _, layers = carry
        mate_row, mate_col, found, phase_layers = mcm_phase(
            row, col, val, row_ptr, n, mate_row, mate_col, levels)
        return mate_row, mate_col, found, layers + phase_layers

    def phase_cond(carry):
        mate_row, _, go, _ = carry
        return go & (mate_row[:n] == n).any()

    if mate_row.shape[0] == n:
        mate_row = jnp.concatenate([jnp.asarray(mate_row, jnp.int32),
                                    jnp.array([n], jnp.int32)])
        mate_col = jnp.concatenate([jnp.asarray(mate_col, jnp.int32),
                                    jnp.array([n], jnp.int32)])
    mate_row, mate_col, _, layers = jax.lax.while_loop(
        phase_cond, phase_body, (mate_row, mate_col, jnp.array(True),
                                 jnp.array(0, jnp.int32))
    )
    return (state_from_mates(row, col, val, n, mate_row, mate_col),
            {"mcm_bfs_layers": layers, "mcm_sorted_layers": layers})


_mcm_counted = _jit_named("mcm", _mcm_phases,
                          static_argnames=("n", "levels"))


def _scan_levels(row, n: int, window_steps: int) -> int:
    """Levels of MCM's sorted-segment scan, ceil(log2(max row degree)),
    from the windowed-search depth resolved for the same rows
    (``_resolve_window_steps``: one round more than that), capped at the
    bound for rows of min(cap, n) entries."""
    cap = int(row.shape[-1])
    return min(window_steps, window_depth(min(cap, n))) - 1


def mcm(row, col, val, n: int, mate_row, mate_col) -> MatchState:
    """Maximum cardinality matching from an initial matching, with the paper's
    weight-aware tie-breaking (heaviest eligible edge chosen as BFS parent).
    Builds ``row_ptr`` and measures the scan's levels from ``row``."""
    levels = _scan_levels(row, n, _resolve_window_steps(row, n, None))
    return _mcm_counted(row, col, val, row_ptr_from_sorted(row, n), n,
                        mate_row, mate_col, levels)[0]


# --------------------------------------------------------------------------
# Phase 3: AWAC — approximate-weight augmenting 4-cycles
# --------------------------------------------------------------------------


@jax.named_scope("awac_select_augment")
def select_and_augment(n, Cgain, Ci, Cw1, Cw2, state: MatchState, min_gain):
    """Steps D + survivor selection + augmentation, given global per-column
    Step-C winners. O(n) dense compute, replicated verbatim on every device in
    the distributed version.

    Cgain [n] f32 (-inf if column unrooted), Ci [n] winner row, Cw1/Cw2 [n]
    weights of the (i,j) and (m_j, m_i) edges of the winning cycle.
    Returns (new_state, n_survivors).
    """
    mate_row, mate_col, u, v = state
    jvec = jnp.arange(n, dtype=jnp.int32)
    rooted = Cgain > NEG
    Ci_s = jnp.clip(Ci, 0, n)  # safe gather index
    e2 = jnp.where(rooted, mate_col[Ci_s], n)  # column of row i's matched edge
    dgain = jnp.where(rooted, Cgain, NEG)
    dg, dj = segment_max_with_payload(dgain, jvec, e2, n + 1)
    surv_c2 = (dg[:n] > NEG) & (~rooted)  # e2-columns whose winner survives
    surv_root = jnp.where(surv_c2, dj[:n], n)
    mask_j = jnp.zeros((n + 1,), bool).at[surv_root].set(True)[:n] & rooted
    n_surv = mask_j.sum()

    # deterministic fallback: single globally-best cycle (paper: random augm.)
    best_j = jnp.argmax(jnp.where(rooted, Cgain, NEG))
    use_fb = (n_surv == 0) & rooted.any()
    mask_j = mask_j | ((jvec == best_j) & use_fb)
    n_surv = n_surv + use_fb.astype(n_surv.dtype)

    # ---- augment all surviving cycles (vertex-disjoint by construction)
    i_ = Ci_s
    r2 = mate_row[:n]  # old mate row of each column j
    c2 = mate_col[i_]  # old mate col of each winner row i
    mj = jnp.where(mask_j, jvec, n)
    mi = jnp.where(mask_j, i_, n)
    mr2 = jnp.where(mask_j, r2, n)
    mc2 = jnp.where(mask_j, c2, n)
    mate_row = mate_row.at[mj].set(jnp.where(mask_j, i_, mate_row[mj]).astype(jnp.int32))
    mate_row = mate_row.at[mc2].set(jnp.where(mask_j, r2, mate_row[mc2]).astype(jnp.int32))
    mate_col = mate_col.at[mi].set(jnp.where(mask_j, jvec, mate_col[mi]).astype(jnp.int32))
    mate_col = mate_col.at[mr2].set(jnp.where(mask_j, c2, mate_col[mr2]).astype(jnp.int32))
    u = u.at[mi].set(jnp.where(mask_j, Cw1, u[mi]))
    u = u.at[mr2].set(jnp.where(mask_j, Cw2, u[mr2]))
    v = v.at[mj].set(jnp.where(mask_j, Cw1, v[mj]))
    v = v.at[mc2].set(jnp.where(mask_j, Cw2, v[mc2]))
    mate_row = mate_row.at[n].set(n)
    mate_col = mate_col.at[n].set(n)
    u = u.at[n].set(0.0)
    v = v.at[n].set(0.0)
    return MatchState(mate_row, mate_col, u, v), n_surv


def awac_candidates(row, col, val, n, state: MatchState, min_gain):
    """Steps A+B on the full edge list: per-edge completion lookup + gain.

    Reference path: global log2(m)-round lex search per edge. The fused sweep
    (``awac_cwinners_fused``) replaces this with a CSR-windowed lookup."""
    mate_row, mate_col, u, v = state
    qr = mate_row[col]  # m_j for each edge's column
    qc = mate_col[row]  # m_i for each edge's row
    pos, found = lex_searchsorted(row, col, qr, qc)
    w2 = jnp.where(found, val[pos], 0.0)
    gain = val + w2 - u[row] - v[col]
    cand = found & (row < n) & (row > qr) & (gain > min_gain)
    return cand, gain, w2


def awac_cwinners(row, col, val, n, state: MatchState, min_gain):
    """Step C on the full edge list: per-column winner (gain, i, w1, w2).

    Reference (seed) implementation — kept as the bit-exactness oracle for
    the fused sweep and still used via ``backend="reference"``."""
    cand, gain, w2 = awac_candidates(row, col, val, n, state, min_gain)
    cap = row.shape[0]
    eidx = jnp.arange(cap, dtype=jnp.int32)
    seg = jnp.where(cand, col, n)
    gm = jnp.where(cand, gain, NEG)
    Cgain_full, Cedge = segment_max_with_payload(gm, eidx, seg, n + 1)
    Cgain, Cedge = Cgain_full[:n], Cedge[:n]
    ce = jnp.clip(Cedge, 0)
    has = Cedge >= 0
    Ci = jnp.where(has, row[ce], n).astype(jnp.int32)
    Cw1 = jnp.where(has, val[ce], 0.0)
    Cw2 = jnp.where(has, w2[ce], 0.0)
    return Cgain, Ci, Cw1, Cw2


def awac_cwinners_fused(row, col, val, row_ptr, n, state: MatchState, min_gain,
                        window_steps: int):
    """Fused Steps A+B+C, XLA path (DESIGN.md §3).

    The completion lookup for (m_j, m_i) is a windowed binary search inside
    row m_j's CSR segment (``window_steps`` rounds ~ log2(max row degree))
    instead of a log2(m)-round global lex search, and Step C's winner
    selection runs as a single packed-key segment reduction when the caller
    traced under x64 (``awac``/``awpm`` do). Bit-identical to
    ``awac_cwinners``."""
    mate_row, mate_col, u, v = state
    cap = row.shape[0]
    qr = mate_row[col]  # m_j for each edge's column
    qc = mate_col[row]  # m_i for each edge's row
    qr_s = jnp.clip(qr, 0, n)
    lo = row_ptr[qr_s]
    # qr == n (unmatched column / padding edge) -> empty window, never found;
    # the reference can "find" the padding entry there but masks it with
    # row < n, so candidate sets agree.
    hi = jnp.where(qr < n, row_ptr[qr_s + 1], lo)
    pos, found = searchsorted_in_window(col, qc, lo, hi, n_steps=window_steps)
    w2 = jnp.where(found, val[jnp.clip(pos, 0, cap - 1)], 0.0)
    gain = val + w2 - u[row] - v[col]
    cand = found & (row < n) & (row > qr) & (gain > min_gain)
    eidx = jnp.arange(cap, dtype=jnp.int32)
    seg = jnp.where(cand, col, n)
    gm = jnp.where(cand, gain, NEG)
    Cgain_full, Cedge = segment_max_with_payload(gm, eidx, seg, n + 1)
    Cgain, Cedge = Cgain_full[:n], Cedge[:n]
    ce = jnp.clip(Cedge, 0)
    has = Cedge >= 0
    Ci = jnp.where(has, row[ce], n).astype(jnp.int32)
    Cw1 = jnp.where(has, val[ce], 0.0)
    Cw2 = jnp.where(has, w2[ce], 0.0)
    return Cgain, Ci, Cw1, Cw2


def _cwinners(backend, row, col, val, row_ptr, n, state, min_gain,
              window_steps):
    if backend == "reference":
        return awac_cwinners(row, col, val, n, state, min_gain)
    if backend == "xla":
        return awac_cwinners_fused(row, col, val, row_ptr, n, state, min_gain,
                                   window_steps)
    raise ValueError(f"unknown AWAC backend {backend!r}")


def resolve_backend(backend: str) -> str:
    """The local AWAC sweep ``backend`` names: ``"auto"`` is the fused XLA
    sweep, any other name stands for itself."""
    return "xla" if backend == "auto" else backend


def _x64_scope(row):
    """The packed-key single-pass reductions (repro.sparse.ops) need an
    x64-enabled TRACE context — but entering ``jax.enable_x64(True)`` in the
    middle of an outer trace promotes fresh loop carries to int64 while existing
    values stay int32 (while_loop carry type mismatch). Inside an outer jit
    the scope is skipped and the two-pass fallback runs instead
    (bit-identical by the sparse.ops contract)."""
    if isinstance(row, jax.core.Tracer):
        return contextlib.nullcontext()
    return jax.enable_x64(True)


def _resolve_window_steps(row, n, window_steps):
    cap = int(row.shape[-1])
    if window_steps is not None:
        ws = int(window_steps)
        # a row holds at most min(cap, n) entries, so a depth covering that
        # bound provably resolves every window — no need to measure
        if ws >= window_depth(min(cap, n)):
            return ws
        # an undersized override is clamped UP: extra depth never changes a
        # windowed-search result, but under-depth would silently miss
        # completion edges — the override may add depth, never break
        # correctness. Under a trace the need cannot be measured, so the
        # provable bound stands in for it.
        if isinstance(row, jax.core.Tracer):
            return window_depth(min(cap, n))
        return max(ws, window_depth(max_row_nnz(row, n)))
    if isinstance(row, jax.core.Tracer):
        return FALLBACK_WINDOW_STEPS
    return window_depth(max_row_nnz(row, n))


def _awac_rounds(row, col, val, row_ptr, n: int, state: MatchState,
                 max_iter: int, min_gain, backend: str, window_steps: int,
                 degrade_infeasible: bool = False):
    """AWAC rounds until one augments nothing or ``max_iter`` have run.
    Returns (state, iters, counters): the 4-cycles augmented (``telemetry``)."""

    def body(carry):
        state, it, _, augmented = carry
        with jax.named_scope("awac_winners"):
            Cgain, Ci, Cw1, Cw2 = _cwinners(
                backend, row, col, val, row_ptr, n, state, min_gain,
                window_steps
            )
        state, n_surv = select_and_augment(n, Cgain, Ci, Cw1, Cw2, state,
                                           min_gain)
        return state, it + 1, n_surv > 0, augmented + n_surv.astype(jnp.int32)

    def cond(carry):
        _, it, go, _ = carry
        return go & (it < max_iter)

    # AWAC rotates 4-cycles — cardinality never changes — so on an
    # imperfect (infeasible-instance) matching every round is pure waste:
    # skip the loop outright when asked to degrade
    go0 = is_perfect(state, n) if degrade_infeasible else jnp.array(True)
    state, iters, _, augmented = jax.lax.while_loop(
        cond, body, (state, jnp.array(0, jnp.int32), go0,
                     jnp.array(0, jnp.int32))
    )
    return state, iters, {"awac_augmented": augmented}


_awac_counted = _jit_named(
    "_awac_loop", _awac_rounds,
    static_argnames=("n", "max_iter", "backend", "window_steps",
                     "degrade_infeasible"))


def _awac_loop(row, col, val, row_ptr, n: int, state: MatchState,
               max_iter: int, min_gain, backend: str, window_steps: int,
               degrade_infeasible: bool = False):
    """The AWAC loop (jitted as ``jit__awac_loop``): (state, iters), its
    counters added to the solve in progress (``telemetry``)."""
    state, iters, counters = _awac_counted(
        row, col, val, row_ptr, n, state, max_iter, min_gain, backend,
        window_steps, degrade_infeasible)
    telemetry.count(counters)
    return state, iters


def awac(row, col, val, n: int, state: MatchState, max_iter: int = 1000,
         min_gain: float = MIN_GAIN, backend: str = "auto",
         row_ptr=None, window_steps: int | None = None,
         degrade_infeasible: bool = False):
    """Full AWAC loop. Returns (state, iters).

    backend: "auto" (= "xla") | "xla" (fused sweep) | "reference" (seed
    jnp path, the bit-exactness oracle). Both produce identical results
    and iteration counts.
    """
    with telemetry.span("repro.window_depth"):
        window_steps = _resolve_window_steps(row, n, window_steps)
    if row_ptr is None:
        row_ptr = telemetry.call("repro.row_ptr", row_ptr_from_sorted, row, n)
    return _awac(row, col, val, row_ptr, n, state, max_iter, min_gain,
                 backend, window_steps, degrade_infeasible)


def _awac(row, col, val, row_ptr, n: int, state: MatchState, max_iter: int,
          min_gain, backend: str, window_steps: int,
          degrade_infeasible: bool):
    """:func:`awac` with ``row_ptr`` built and ``window_steps`` resolved."""
    backend = resolve_backend(backend)
    # With "xla", an x64-enabled trace context lets Step C run as ONE
    # packed-key uint64 segment_max (see repro.sparse.ops); inputs/outputs
    # stay f32/i32. Under an outer jit the scope is a no-op (see _x64_scope).
    with _x64_scope(row) if backend == "xla" else contextlib.nullcontext():
        return telemetry.call(
            "repro.awac", _awac_loop, row, col, val, row_ptr, n, state,
            max_iter, min_gain, backend, window_steps, degrade_infeasible)


def _awpm(row, col, val, n: int, max_iter: int = 1000,
          min_gain: float = MIN_GAIN, backend: str = "auto",
          window_steps: int | None = None,
          degrade_infeasible: bool = False):
    """Full pipeline: greedy maximal -> MCM -> AWAC. Returns (state, awac_iters).

    Internal engine behind ``repro.core.api.solve`` (the single-instance
    dispatch target) and the deprecated ``awpm`` shim. The rows' max degree
    is measured once, while the device runs greedy; it sets both MCM's scan
    levels and AWAC's search depth, and ``row_ptr`` serves both phases.
    """
    st, counters = telemetry.call("repro.greedy", _greedy_counted, row, col,
                                  val, n)
    telemetry.count(counters)
    with telemetry.span("repro.window_depth"):
        window_steps = _resolve_window_steps(row, n, window_steps)
    row_ptr = telemetry.call("repro.row_ptr", row_ptr_from_sorted, row, n)
    st, counters = telemetry.call(
        "repro.mcm", _mcm_counted, row, col, val, row_ptr, n, st.mate_row,
        st.mate_col, _scan_levels(row, n, window_steps))
    telemetry.count(counters)
    return _awac(row, col, val, row_ptr, n, st, max_iter, min_gain, backend,
                 window_steps, degrade_infeasible)


def awpm(row, col, val, n: int, max_iter: int = 1000, min_gain: float = MIN_GAIN,
         backend: str = "auto"):
    """Deprecated alias of the full pipeline — use ``repro.core.api.solve``."""
    warn_legacy("repro.core.single.awpm", "solve()")
    return _awpm(row, col, val, n, max_iter=max_iter, min_gain=min_gain,
                 backend=backend)
