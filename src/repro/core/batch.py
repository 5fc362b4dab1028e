"""Batched AWPM engine: one-shot matching of B instances in one dispatch.

The paper's motivating workloads (static pivoting for sparse direct solvers,
per-group MoE routing) need *many* heavy-weight perfect matchings at once.
This module solves a whole batch of padded [B, cap] COO instances (shared n,
per-instance edge lists; padding entries (n, n, 0)) with per-instance
convergence masks inside single ``lax.while_loop``s — no python loop over
instances, no per-instance jit dispatch (DESIGN.md §4).

Bit-exactness contract: for every instance b and every backend,
``awpm_batched(row, col, val, n)`` produces exactly the arrays
``core.single.awpm(row[b], col[b], val[b], n)`` would. The greedy/MCM round
bodies here are ``single.greedy_round`` / ``single.mcm_phase`` re-expressed
on the flat batched segment primitives
(``sparse.ops.batched_segment_max_with_payload`` etc.) — kept in sync with
single.py by the differential suite — while ``single.select_and_augment``
and the "reference" Step C are vmapped verbatim. A converged instance's
state is frozen by the mask while the rest of the batch keeps iterating. Extra windowed-search depth (the batch measures one
``window_steps`` across all instances) never changes a search result, so the
shared depth preserves per-instance bit-identity.

Backends mirror ``core.single.awac``:
  reference — vmapped seed oracle (global lex search per instance)
  xla       — flat batched fused sweep: one offset-segment reduction and one
              offset-window search over all B * cap edges (production CPU)
  pallas    — batch-grid ``awac_sweep`` kernel, batch as leading grid axis
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import single
from repro.core._compat import warn_legacy
from repro.core.single import MIN_GAIN, NEG, MatchState
from repro.sparse.csr import batched_row_ptr_from_sorted
from repro.sparse.ops import (
    batched_searchsorted_in_window,
    batched_segment_max_with_payload,
    batched_segment_min,
)


def stack_graphs(graphs):
    """Pad a list of BipartiteGraphs (shared n, arbitrary per-instance nnz)
    into batched [B, cap] (row, col, val) jnp arrays with a common capacity.
    Extra slots are padding edges (n, n, 0), which every phase drops."""
    n = graphs[0].n
    if any(g.n != n for g in graphs):
        raise ValueError("all instances in a batch must share n")
    cap = max(g.capacity for g in graphs)
    b = len(graphs)
    row = np.full((b, cap), n, np.int32)
    col = np.full((b, cap), n, np.int32)
    val = np.zeros((b, cap), np.float32)
    for i, g in enumerate(graphs):
        row[i, : g.capacity] = g.row
        col[i, : g.capacity] = g.col
        val[i, : g.capacity] = g.val
    return jnp.asarray(row), jnp.asarray(col), jnp.asarray(val)


def empty_mates(b: int, n: int):
    full = jnp.full((b, n + 1), n, jnp.int32)
    return full, full


def matching_weight_batched(state: MatchState, n: int) -> jnp.ndarray:
    """Per-instance matching weight [B]."""
    return single.ordered_sum(state.u[:, :n])


def is_perfect_batched(state: MatchState, n: int) -> jnp.ndarray:
    """Per-instance perfect-matching flag [B]."""
    return (state.mate_row[:, :n] < n).all(axis=1)


def state_from_mates_batched(row, col, val, n: int, mate_row,
                             mate_col) -> MatchState:
    """Batched ``single.state_from_mates``: fields are [B, n + 1]."""
    return jax.vmap(
        lambda r, c, v, mr, mc: single.state_from_mates(r, c, v, n, mr, mc)
    )(row, col, val, mate_row, mate_col)


@functools.partial(jax.jit, static_argnames=("n", "window_steps"))
def _state_from_mates_windowed(row, col, val, row_ptr, n: int, mate_row,
                               mate_col, window_steps: int) -> MatchState:
    """``state_from_mates_batched`` with the matched-edge weight lookup as a
    CSR-windowed search inside each row's own segment (log2(max degree)
    rounds) instead of the 32-round global lex search. Identical output:
    (row i, mate_col[i]) is a unique key, so a found position — and the
    not-found zero — agree with the lex path."""
    b, cap = row.shape
    mate_row = mate_row.astype(jnp.int32)
    mate_col = mate_col.astype(jnp.int32)
    pos, found = batched_searchsorted_in_window(
        col, mate_col[:, :n], row_ptr[:, :n], row_ptr[:, 1 : n + 1],
        n_steps=window_steps,
    )
    uu = jnp.where(
        found, jnp.take_along_axis(val, jnp.clip(pos, 0, cap - 1), axis=1),
        0.0)
    u = jnp.zeros((b, n + 1), jnp.float32).at[:, :n].set(uu)
    v = jnp.zeros((b, n + 1), jnp.float32).at[:, :n].set(
        jnp.where(mate_row[:, :n] < n,
                  jnp.take_along_axis(u, jnp.clip(mate_row[:, :n], 0, n),
                                      axis=1), 0.0)
    )
    return MatchState(mate_row, mate_col, u, v)


# --------------------------------------------------------------------------
# Phase 1: batched greedy weighted maximal matching
# --------------------------------------------------------------------------


def greedy_propose_full(row, col, val, n: int, mate_row, mate_col):
    """Per-column best available proposal from the full batched edge list:
    (pv [B, n] score with NEG where none, prow [B, n] proposing row with
    sentinel n). The distributed-batched engine (core/dist.py) computes the
    same two arrays from 2D blocks + collectives and feeds them to the same
    ``greedy_commit`` — that split is what keeps the two engines
    bit-identical by construction."""
    b, cap = row.shape
    eidx = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32), (b, cap))
    avail = (row < n) & (jnp.take_along_axis(mate_col, row, axis=1) == n) \
        & (jnp.take_along_axis(mate_row, col, axis=1) == n)
    score = jnp.where(avail, val, NEG)
    seg = jnp.where(avail, col, n)
    pg, pe = batched_segment_max_with_payload(score, eidx, seg, n + 1)
    has = pe[:, :n] >= 0
    prow = jnp.where(
        has, jnp.take_along_axis(row, jnp.clip(pe[:, :n], 0), axis=1), n)
    pv = jnp.where(has, pg[:, :n], NEG)
    return pv, prow


def greedy_commit(pv, prow, n: int, mate_row, mate_col, active):
    """Replicated per-row contest + mate scatter of one greedy proposal
    round (shared verbatim with the distributed-batched engine). Frozen
    instances accept nothing. Returns (mate_row, mate_col, active)."""
    b = pv.shape[0]
    jvec = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
    ivec = jnp.arange(n, dtype=jnp.int32)
    bidx = jnp.arange(b)[:, None]
    _, rj = batched_segment_max_with_payload(pv, jvec, prow, n + 1)
    ok = (rj[:, :n] >= 0) & active[:, None]
    wcol = jnp.where(ok, rj[:, :n], n).astype(jnp.int32)
    mate_col = mate_col.at[bidx, jnp.where(ok, ivec[None, :], n)].set(wcol)
    mate_row = mate_row.at[bidx, wcol].set(
        jnp.where(ok, ivec[None, :], n).astype(jnp.int32))
    mate_col = mate_col.at[:, n].set(n)
    mate_row = mate_row.at[:, n].set(n)
    return mate_row, mate_col, active & ok.any(axis=1)


def greedy_loop(n: int, b: int, propose_fn):
    """Greedy proposal rounds for B instances in one while_loop with
    per-instance convergence masks. ``propose_fn(mate_row, mate_col) ->
    (pv, prow)`` supplies each round's per-column proposals — the full edge
    list here, blocks + collectives in the distributed engine. Returns
    (mate_row, mate_col), each [B, n + 1]."""

    def round_body(carry):
        mate_row, mate_col, active = carry
        pv, prow = propose_fn(mate_row, mate_col)
        return greedy_commit(pv, prow, n, mate_row, mate_col, active)

    def cond(carry):
        return carry[2].any()

    mr0, mc0 = empty_mates(b, n)
    mate_row, mate_col, _ = jax.lax.while_loop(
        cond, round_body, (mr0, mc0, jnp.ones((b,), bool))
    )
    return mate_row, mate_col


def greedy_maximal_batched(row, col, val, n: int):
    """``single.greedy_maximal``'s proposal rounds for all instances in one
    while_loop: each round is ``single.greedy_round`` re-expressed on the
    flat offset-segment reductions, and instances whose round proposes
    nothing go inactive (their mates freeze). Returns (mate_row, mate_col),
    each [B, n + 1].

    Traced under x64 so both per-round reductions run as single packed-key
    passes (bit-identical to the two-pass reference — sparse.ops); under an
    outer jit the scope is a no-op and the two-pass fallback runs (see
    ``single._x64_scope``)."""
    with single._x64_scope(row):
        return _greedy_maximal_batched(row, col, val, n)


@functools.partial(jax.jit, static_argnames=("n",))
def _greedy_maximal_batched(row, col, val, n: int):
    b = row.shape[0]
    return greedy_loop(
        n, b, functools.partial(greedy_propose_full, row, col, val, n))


# --------------------------------------------------------------------------
# Phase 2: batched maximum cardinality matching
# --------------------------------------------------------------------------


def bfs_parents_full(row, col, val, n: int, frontier, visited):
    """Per-row BFS parent proposals (new [B, n] mask, pcol [B, n] — valid
    only where ``new``) from the full batched edge list. The distributed
    engine computes the same arrays from 2D blocks + collectives and feeds
    the same ``bfs_commit``."""
    b, cap = row.shape
    eidx = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32), (b, cap))
    elig = (row < n) & jnp.take_along_axis(frontier, col, axis=1) \
        & (~jnp.take_along_axis(visited, row, axis=1))
    score = jnp.where(elig, val, NEG)
    seg = jnp.where(elig, row, n)
    _, re = batched_segment_max_with_payload(score, eidx, seg, n + 1)
    new = re[:, :n] >= 0
    pcol = jnp.take_along_axis(col, jnp.clip(re[:, :n], 0), axis=1)
    return new, pcol


def bfs_commit(new, pcol, n: int, mate_col, parent_col, visited):
    """One BFS layer's replicated state update (shared verbatim with the
    distributed-batched engine). Returns (parent_col, visited, frontier,
    found)."""
    b = new.shape[0]
    bidx = jnp.arange(b)[:, None]
    pc = jnp.where(new, pcol, parent_col[:, :n])
    parent_col = parent_col.at[:, :n].set(pc.astype(jnp.int32))
    visited = visited.at[:, :n].set(visited[:, :n] | new)
    free_new = new & (mate_col[:, :n] == n)
    found = free_new.any(axis=1)
    nf_idx = jnp.where(new & ~free_new, mate_col[:, :n], n)
    frontier = jnp.zeros((b, n + 1), bool).at[bidx, nf_idx].set(True) \
        .at[:, n].set(False)
    return parent_col, visited, frontier, found


def mcm_bfs_loop(n: int, b: int, mate_row, mate_col, parents_fn):
    """Layered BFS for all instances in one while_loop: per-instance layer
    counts, found flags, and progress masks. ``parents_fn(frontier,
    visited) -> (new, pcol)`` supplies each layer's per-row parent winners
    (full edge list here; blocks + collectives in core.dist). An instance
    whose own BFS terminated (found / stalled / layer bound) freezes while
    deeper searches continue. Returns (parent_col, visited, found, layers),
    leading dim B."""
    frontier0 = jnp.zeros((b, n + 1), bool).at[:, :n].set(
        mate_row[:, :n] == n)
    parent_col0 = jnp.full((b, n + 1), n, jnp.int32)
    visited0 = jnp.zeros((b, n + 1), bool)

    def act_of(found, layers, progressed):
        return (~found) & progressed & (layers <= n)

    def bfs_body(carry):
        frontier, parent_col, visited, found, layers, progressed = carry
        act = act_of(found, layers, progressed)
        new, pcol = parents_fn(frontier, visited)
        parent_col2, visited2, frontier2, found2 = bfs_commit(
            new, pcol, n, mate_col, parent_col, visited)
        keep = act[:, None]
        return (jnp.where(keep, frontier2, frontier),
                jnp.where(keep, parent_col2, parent_col),
                jnp.where(keep, visited2, visited),
                jnp.where(act, found2, found),
                layers + act.astype(jnp.int32),
                jnp.where(act, new.any(axis=1), progressed))

    def bfs_cond(carry):
        _, _, _, found, layers, progressed = carry
        return act_of(found, layers, progressed).any()

    frontier, parent_col, visited, found, layers, _ = jax.lax.while_loop(
        bfs_cond, bfs_body,
        (frontier0, parent_col0, visited0, jnp.zeros((b,), bool),
         jnp.zeros((b,), jnp.int32), jnp.ones((b,), bool)),
    )
    return parent_col, visited, found, layers


def _mcm_bfs_batched(row, col, val, n: int, mate_row, mate_col):
    """``single._mcm_bfs`` for all instances in one while_loop (see
    ``mcm_bfs_loop``)."""
    b = row.shape[0]
    return mcm_bfs_loop(
        n, b, mate_row, mate_col,
        functools.partial(bfs_parents_full, row, col, val, n))


def trace_and_flip_batched(parent_col, visited, found, layers, mate_row,
                           mate_col, n: int):
    """Batched ``single.trace_and_flip``: lockstep backtrace with per-column
    claims then flips, each loop running to every instance's own ``layers``
    bound with per-instance masks (flat offset segment_min for the claims)."""
    b = parent_col.shape[0]
    widx = jnp.broadcast_to(jnp.arange(n + 1, dtype=jnp.int32), (b, n + 1))
    bidx = jnp.arange(b)[:, None]
    endpoints = (jnp.zeros((b, n + 1), bool).at[:, :n].set(
        visited[:, :n] & (mate_col[:, :n] == n))) & found[:, None]

    def claim_body(carry):
        active, cur, t = carry
        run = t < layers
        j_w = jnp.where(active, jnp.take_along_axis(parent_col, cur, axis=1),
                        n)
        win = batched_segment_min(widx, j_w, n + 1)
        active2 = active & (jnp.take_along_axis(win, j_w, axis=1) == widx)
        nxt = jnp.take_along_axis(mate_row, j_w, axis=1)
        cur2 = jnp.where(active2 & (nxt < n), nxt, cur)
        keep = run[:, None]
        return (jnp.where(keep, active2, active),
                jnp.where(keep, cur2, cur), t + run.astype(jnp.int32))

    active, _, _ = jax.lax.while_loop(
        lambda c: (c[2] < layers).any(), claim_body,
        (endpoints, widx, jnp.zeros((b,), jnp.int32)),
    )

    def flip_body(carry):
        surv, cur, mate_row, mate_col, t = carry
        run = t < layers
        j = jnp.where(surv, jnp.take_along_axis(parent_col, cur, axis=1), n)
        prev = jnp.take_along_axis(mate_row, j, axis=1)
        mr2 = mate_row.at[bidx, j].set(
            jnp.where(surv, cur, prev).astype(jnp.int32))
        mc2 = mate_col.at[bidx, jnp.where(surv, cur, n)].set(
            j.astype(jnp.int32))
        mr2 = mr2.at[:, n].set(n)
        mc2 = mc2.at[:, n].set(n)
        surv2 = surv & (prev < n)
        cur2 = jnp.where(surv2, prev, cur)
        keep = run[:, None]
        return (jnp.where(keep, surv2, surv), jnp.where(keep, cur2, cur),
                jnp.where(keep, mr2, mate_row),
                jnp.where(keep, mc2, mate_col), t + run.astype(jnp.int32))

    _, _, mate_row, mate_col, _ = jax.lax.while_loop(
        lambda c: (c[4] < layers).any(), flip_body,
        (active, widx, mate_row, mate_col, jnp.zeros((b,), jnp.int32)),
    )
    return mate_row, mate_col


def mcm_batched(row, col, val, n: int, mate_row, mate_col):
    """Batched MCM: one masked phase loop over the flat-batched
    BFS + trace/flip bodies (``single.mcm_phase`` re-expressed on the
    offset-segment primitives). Returns (mate_row, mate_col).

    Traced under x64 so each BFS layer's winner reduction runs as a single
    packed-key pass (bit-identical to the two-pass reference); no-op under
    an outer jit (see ``single._x64_scope``)."""
    with single._x64_scope(row):
        return _mcm_batched(row, col, val, n, mate_row, mate_col)


def mcm_loop(n: int, b: int, mate_row, mate_col, parents_fn):
    """Masked MCM phase loop over the batched BFS + trace/flip bodies,
    parameterized by the per-layer parent selection (``parents_fn``, see
    ``mcm_bfs_loop``) so the distributed-batched engine shares every mask
    and commit verbatim. Returns (mate_row, mate_col)."""

    def body(carry):
        mr, mc, active = carry
        parent_col, visited, found, layers = mcm_bfs_loop(
            n, b, mr, mc, parents_fn)
        # frozen instances trace nothing: zero their layer counts + found
        found = found & active
        layers = jnp.where(active, layers, 0)
        mr2, mc2 = trace_and_flip_batched(parent_col, visited, found, layers,
                                          mr, mc, n)
        keep = active[:, None]
        mr = jnp.where(keep, mr2, mr)
        mc = jnp.where(keep, mc2, mc)
        active = active & found & (mr[:, :n] == n).any(axis=1)
        return mr, mc, active

    def cond(carry):
        return carry[2].any()

    active0 = (mate_row[:, :n] == n).any(axis=1)
    mate_row, mate_col, _ = jax.lax.while_loop(
        cond, body, (mate_row, mate_col, active0)
    )
    return mate_row, mate_col


@functools.partial(jax.jit, static_argnames=("n",))
def _mcm_batched(row, col, val, n: int, mate_row, mate_col):
    b = row.shape[0]
    return mcm_loop(n, b, mate_row, mate_col,
                    functools.partial(bfs_parents_full, row, col, val, n))


# --------------------------------------------------------------------------
# Phase 3: batched AWAC
# --------------------------------------------------------------------------


def awac_cwinners_fused_batched(row, col, val, row_ptr, n: int,
                                state: MatchState, min_gain,
                                window_steps: int):
    """Flat batched fused Steps A+B+C: the [B, cap] edge streams are treated
    as one B * cap edge list with per-instance offset windows
    (``batched_searchsorted_in_window``) and offset segments
    (``batched_segment_max_with_payload``) — one reduction pass for the whole
    batch, bit-identical per instance to ``single.awac_cwinners_fused``."""
    mate_row, mate_col, u, v = state
    b, cap = row.shape
    qr = jnp.take_along_axis(mate_row, col, axis=1)  # m_j for each edge
    qc = jnp.take_along_axis(mate_col, row, axis=1)  # m_i for each edge
    qr_s = jnp.clip(qr, 0, n)
    lo = jnp.take_along_axis(row_ptr, qr_s, axis=1)
    hi = jnp.where(qr < n, jnp.take_along_axis(row_ptr, qr_s + 1, axis=1), lo)
    pos, found = batched_searchsorted_in_window(col, qc, lo, hi,
                                                n_steps=window_steps)
    w2 = jnp.where(
        found,
        jnp.take_along_axis(val, jnp.clip(pos, 0, cap - 1), axis=1), 0.0)
    gain = val + w2 - jnp.take_along_axis(u, row, axis=1) \
        - jnp.take_along_axis(v, col, axis=1)
    cand = found & (row < n) & (row > qr) & (gain > min_gain)
    eidx = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32), (b, cap))
    seg = jnp.where(cand, col, n)
    gm = jnp.where(cand, gain, NEG)
    Cgain_full, Cedge = batched_segment_max_with_payload(gm, eidx, seg, n + 1)
    Cgain, Cedge = Cgain_full[:, :n], Cedge[:, :n]
    ce = jnp.clip(Cedge, 0)
    has = Cedge >= 0
    Ci = jnp.where(has, jnp.take_along_axis(row, ce, axis=1), n) \
        .astype(jnp.int32)
    Cw1 = jnp.where(has, jnp.take_along_axis(val, ce, axis=1), 0.0)
    Cw2 = jnp.where(has, jnp.take_along_axis(w2, ce, axis=1), 0.0)
    return Cgain, Ci, Cw1, Cw2


def _cwinners_batched(backend, row, col, val, row_ptr, n, state, min_gain,
                      window_steps):
    if backend == "reference":
        return jax.vmap(
            lambda r, c, v, mr, mc, u, vv: single.awac_cwinners(
                r, c, v, n, MatchState(mr, mc, u, vv), min_gain)
        )(row, col, val, *state)
    if backend == "xla":
        return awac_cwinners_fused_batched(row, col, val, row_ptr, n, state,
                                           min_gain, window_steps)
    if backend == "pallas":
        # Local import: core must stay importable without the kernel package.
        from repro.kernels.cycle_gain.ops import awac_sweep_winners_batched

        return awac_sweep_winners_batched(
            row, col, val, row_ptr, state.mate_row, state.mate_col, state.u,
            state.v, min_gain, n=n, window_steps=window_steps,
        )
    raise ValueError(f"unknown AWAC backend {backend!r}")


# Trace-time convergence-mask hook for the chaos harness
# (``runtime.chaos``): when set, called as ``tap(active, iters) -> active``
# after each round's convergence update. None in production — the branch
# below folds away entirely at trace time.
_CONVERGENCE_TAP = None


def awac_loop(n: int, state: MatchState, max_iter: int, min_gain,
              cwinners_fn, active0=None, aux0=None):
    """Masked batched AWAC loop. ``cwinners_fn(state) -> (Cgain, Ci, Cw1,
    Cw2, aux)`` supplies each round's Step A+B+C winners plus an int32
    value accumulated across rounds (scalar 0 for the local backends; the
    dropped-candidate count — or the [dropped, integrity] pair under
    exchange checking — for the distributed engine's bucketed exchanges).
    Step D + augmentation is the vmapped ``single.select_and_augment`` —
    shared verbatim with every other engine.

    ``active0`` ([B] bool) masks instances out of the loop from round 0
    (the infeasible-instance short-circuit: an imperfect matching can never
    become perfect through 4-cycle rotations). ``aux0`` overrides the aux
    accumulator's initial value/shape. Returns (state, iters [B], aux)."""
    b = state.mate_row.shape[0]
    select = jax.vmap(
        lambda Cg, Ci, Cw1, Cw2, mr, mc, u, v: single.select_and_augment(
            n, Cg, Ci, Cw1, Cw2, MatchState(mr, mc, u, v), min_gain)
    )

    def body(carry):
        state, iters, active, aux = carry
        Cgain, Ci, Cw1, Cw2, a = cwinners_fn(state)
        new_state, n_surv = select(Cgain, Ci, Cw1, Cw2, *state)
        keep = active[:, None]
        state = MatchState(
            *(jnp.where(keep, ns, s) for ns, s in zip(new_state, state)))
        iters = iters + active.astype(jnp.int32)
        active = active & (n_surv > 0) & (iters < max_iter)
        if _CONVERGENCE_TAP is not None:
            active = _CONVERGENCE_TAP(active, iters)
        return state, iters, active, aux + a

    def cond(carry):
        return carry[2].any()

    # max_iter <= 0 admits no iterations, matching single._awac_loop
    go0 = jnp.full((b,), max_iter > 0)
    if active0 is not None:
        go0 = go0 & active0
    state, iters, _, aux = jax.lax.while_loop(
        cond, body,
        (state, jnp.zeros((b,), jnp.int32), go0,
         jnp.array(0, jnp.int32) if aux0 is None else aux0),
    )
    return state, iters, aux


@functools.partial(
    jax.jit, static_argnames=("n", "max_iter", "backend", "window_steps",
                              "degrade_infeasible")
)
def _awac_loop_batched(row, col, val, row_ptr, n: int, state: MatchState,
                       max_iter: int, min_gain, backend: str,
                       window_steps: int, degrade_infeasible: bool = False):
    def cwinners(st):
        out = _cwinners_batched(backend, row, col, val, row_ptr, n, st,
                                min_gain, window_steps)
        return (*out, jnp.array(0, jnp.int32))

    active0 = is_perfect_batched(state, n) if degrade_infeasible else None
    state, iters, _ = awac_loop(n, state, max_iter, min_gain, cwinners,
                                active0=active0)
    return state, iters


def _resolve_window_steps_batched(row, n, window_steps):
    # csr.max_row_nnz measures [B, cap] rows across the whole batch (one
    # shared static depth; extra rounds beyond an instance's own need never
    # change its search results), so the single-instance resolver applies.
    return single._resolve_window_steps(row, n, window_steps)


def awac_batched(row, col, val, n: int, state: MatchState,
                 max_iter: int = 1000, min_gain: float = MIN_GAIN,
                 backend: str = "auto", row_ptr=None,
                 window_steps: int | None = None,
                 degrade_infeasible: bool = False):
    """Batched AWAC loop over [B, cap] instances. Returns (state, iters [B]).

    Same backend contract as ``single.awac``; every instance's result and
    iteration count are bit-identical to its own single-instance run."""
    backend = single.resolve_backend(backend, n=n, batch=row.shape[0])
    window_steps = _resolve_window_steps_batched(row, n, window_steps)
    if row_ptr is None:
        row_ptr = batched_row_ptr_from_sorted(row, n)
    if backend == "pallas_persistent":
        # Local import: core must stay importable without the kernel package.
        from repro.kernels.cycle_gain.ops import awac_persistent_loop_batched

        b = row.shape[0]
        go0 = is_perfect_batched(state, n) if degrade_infeasible \
            else jnp.ones((b,), bool)
        mr, mc, u, v, iters = awac_persistent_loop_batched(
            row, col, val, row_ptr, state.mate_row, state.mate_col, state.u,
            state.v, min_gain, go0, n=n, window_steps=window_steps,
            max_iter=max_iter)
        return MatchState(mr, mc, u, v), iters
    if backend == "xla":
        # Same x64 trace context as single.awac: Step C runs as one
        # packed-key uint64 segment_max over the whole batch (no-op under
        # an outer jit, see single._x64_scope).
        with single._x64_scope(row):
            return _awac_loop_batched(row, col, val, row_ptr, n, state,
                                      max_iter, min_gain, backend,
                                      window_steps, degrade_infeasible)
    return _awac_loop_batched(row, col, val, row_ptr, n, state, max_iter,
                              min_gain, backend, window_steps,
                              degrade_infeasible)


# --------------------------------------------------------------------------
# Warm-start rematching: seed the pipeline from previous mate arrays
# --------------------------------------------------------------------------


def _normalize_mates_batched(mate_row, mate_col, b: int, n: int):
    """Accept seed mates of shape [B, n] or [B, n + 1] (numpy or jnp, any
    int dtype) and return int32 [B, n + 1] arrays with the sentinel slot
    pinned. Shape mismatches raise ValueError — the caller decides whether
    that means \"fall back to cold\" (serving) or \"user error\" (api)."""
    mate_row = jnp.asarray(mate_row, jnp.int32)
    mate_col = jnp.asarray(mate_col, jnp.int32)
    if mate_row.shape != mate_col.shape:
        raise ValueError(
            f"warm-start mate arrays disagree: mate_row {mate_row.shape} vs "
            f"mate_col {mate_col.shape}")
    if mate_row.shape == (b, n):
        pad = jnp.full((b, 1), n, jnp.int32)
        mate_row = jnp.concatenate([mate_row, pad], axis=1)
        mate_col = jnp.concatenate([mate_col, pad], axis=1)
    elif mate_row.shape != (b, n + 1):
        raise ValueError(
            f"warm-start mate arrays must be [B, n] or [B, n + 1] = "
            f"[{b}, {n + 1}], got {mate_row.shape}")
    return (mate_row.at[:, n].set(n), mate_col.at[:, n].set(n))


@functools.partial(jax.jit, static_argnames=("n", "window_steps"))
def repair_mates_batched(row, col, val, row_ptr, n: int, mate_row, mate_col,
                         window_steps: int):
    """Repair seed mates against the CURRENT edge lists: a claimed pair
    (i, j) survives only if it is mutual (``mate_col[i] == j``) and the
    edge still exists in the instance (CSR-windowed membership probe). Any
    out-of-range, one-sided, or structurally-stale entry is unmatched on
    both sides, so the output is always a partial matching on existing
    edges — whatever garbage the seed carried. Returns (mate_row,
    mate_col), int32 [B, n + 1]."""
    b = row.shape[0]
    jvec = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
    mr = mate_row[:, :n]
    valid = (mr >= 0) & (mr < n)
    i_s = jnp.clip(mr, 0, n)
    lo = jnp.take_along_axis(row_ptr, i_s, axis=1)
    hi = jnp.where(valid, jnp.take_along_axis(row_ptr, i_s + 1, axis=1), lo)
    _, found = batched_searchsorted_in_window(col, jvec, lo, hi,
                                              n_steps=window_steps)
    mutual = valid & (jnp.take_along_axis(mate_col, i_s, axis=1) == jvec)
    keep = mutual & found
    bidx = jnp.arange(b)[:, None]
    new_mr = jnp.full((b, n + 1), n, jnp.int32).at[:, :n].set(
        jnp.where(keep, mr, n))
    new_mc = jnp.full((b, n + 1), n, jnp.int32).at[
        bidx, jnp.where(keep, i_s, n)].set(jnp.where(keep, jvec, n))
    return new_mr.at[:, n].set(n), new_mc.at[:, n].set(n)


def warm_mates_batched(row, col, val, row_ptr, n: int, mate_row, mate_col,
                       window_steps: int):
    """Repaired seed + bounded MCM top-up: the warm-start replacement for
    the greedy + MCM cold phases. The top-up is the pipeline's own batched
    MCM, whose phase loop is bounded by the seed deficiency (each phase
    either matches a free row or stops) — an intact seed runs ZERO phases,
    which is where warm-start rematching earns its keep on mostly-stable
    streams. Returns (mate_row, mate_col)."""
    mate_row, mate_col = repair_mates_batched(
        row, col, val, row_ptr, n, mate_row, mate_col, window_steps)
    return mcm_batched(row, col, val, n, mate_row, mate_col)


def _awpm_batched_from_state(row, col, val, n: int, mate_row, mate_col,
                             max_iter: int = 1000,
                             min_gain: float = MIN_GAIN, backend: str = "auto",
                             row_ptr=None, window_steps: int | None = None,
                             degrade_infeasible: bool = False):
    """Warm-start batched pipeline: repair the seed mates -> MCM top-up ->
    AWAC, replacing greedy + MCM-from-scratch (DESIGN.md §11). Returns
    (MatchState, awac_iters [B]), same contract as ``_awpm_batched``.

    When the seed IS an AWAC fixed point of the same instance (the
    previous result of an unchanged problem), repair keeps every pair, the
    top-up runs zero phases, and AWAC converges on its first round —
    returning the seed matching (mates, duals, weight) bit-identically."""
    window_steps = _resolve_window_steps_batched(row, n, window_steps)
    if row_ptr is None:
        row_ptr = batched_row_ptr_from_sorted(row, n)
    mate_row, mate_col = _normalize_mates_batched(
        mate_row, mate_col, row.shape[0], n)
    mate_row, mate_col = warm_mates_batched(
        row, col, val, row_ptr, n, mate_row, mate_col, window_steps)
    state = _state_from_mates_windowed(row, col, val, row_ptr, n, mate_row,
                                       mate_col, window_steps)
    return awac_batched(row, col, val, n, state, max_iter=max_iter,
                        min_gain=min_gain, backend=backend, row_ptr=row_ptr,
                        window_steps=window_steps,
                        degrade_infeasible=degrade_infeasible)


def awpm_batched(row, col, val, n: int, max_iter: int = 1000,
                 min_gain: float = MIN_GAIN, backend: str = "auto",
                 row_ptr=None, window_steps: int | None = None):
    """Deprecated alias of the batched pipeline — use ``repro.core.api.solve``
    with a batched ``MatchingProblem``."""
    warn_legacy("repro.core.batch.awpm_batched", "solve()")
    return _awpm_batched(row, col, val, n, max_iter=max_iter,
                         min_gain=min_gain, backend=backend, row_ptr=row_ptr,
                         window_steps=window_steps)


def _awpm_batched(row, col, val, n: int, max_iter: int = 1000,
                  min_gain: float = MIN_GAIN, backend: str = "auto",
                  row_ptr=None, window_steps: int | None = None,
                  degrade_infeasible: bool = False):
    """Full batched pipeline: greedy maximal -> MCM -> AWAC for B instances
    in three dispatches total. row/col/val are [B, cap] padded lex-sorted COO
    sharing n (see ``stack_graphs``). Returns (MatchState with [B, n + 1]
    fields, awac_iters [B]) — per instance bit-identical to
    ``single._awpm(row[b], col[b], val[b], n)`` on the same backend.

    Internal engine behind ``repro.core.api.solve`` (the batched dispatch
    target) and the deprecated ``awpm_batched`` shim."""
    window_steps = _resolve_window_steps_batched(row, n, window_steps)
    if row_ptr is None:
        row_ptr = batched_row_ptr_from_sorted(row, n)
    mate_row, mate_col = greedy_maximal_batched(row, col, val, n)
    mate_row, mate_col = mcm_batched(row, col, val, n, mate_row, mate_col)
    state = _state_from_mates_windowed(row, col, val, row_ptr, n, mate_row,
                                       mate_col, window_steps)
    return awac_batched(row, col, val, n, state, max_iter=max_iter,
                        min_gain=min_gain, backend=backend, row_ptr=row_ptr,
                        window_steps=window_steps,
                        degrade_infeasible=degrade_infeasible)
