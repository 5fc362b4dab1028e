"""Distributed-memory AWPM via shard_map over a 2D(+pod) device grid.

The paper's √p x √p process grid maps onto the production mesh:
grid row  a  = flattened index over ``row_axes``   (e.g. ("pod", "data")),
grid col  b  = index over ``col_axis``             ("model").

O(m) edge state is strictly 2D-block-sharded ([Pr, Pc, cap] stacked blocks,
global indices, lex-sorted per block). O(n) matching state (mates, u, v,
winners) is replicated and updated identically on every device, so steps C/D
need only all_gathers and the augmentation broadcast of the paper (Alg. 6)
disappears entirely (DESIGN.md §2).

Communication per AWAC round (paper Steps A-D):
  A/B: two bucketed fixed-capacity ``all_to_all``s (first along the column
       axis, then along the row axes) carrying relabeled completion edges
       (i', j') = (mate_row[c], mate_col[r]) — the nonzeros of M Aᵀ M.
  C:   all_gather of per-local-column winners along ``row_axes``.
  D:   all_gather along ``col_axis`` to replicate the winner arrays, then the
       replicated `select_and_augment` from repro.core.single (shared code).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.core import batch, single, telemetry
from repro.core._compat import warn_legacy
from repro.core.single import MIN_GAIN, NEG, MatchState
from repro.sparse.csr import max_row_nnz, window_depth
from repro.sparse.ops import (
    batched_searchsorted_in_window,
    batched_segment_argmax_tie,
    batched_sorted_segment_max_with_payload,
    lex_searchsorted,
    searchsorted_in_window,
    segment_argmax_tie,
    segment_max_with_payload,
)
from repro.sparse.partition import partition_coo_2d, partition_coo_2d_batched

_shard_map = functools.partial(jax.shard_map, check_vma=False)


def make_mesh(shape, axes=("data", "model")):
    """``jax.make_mesh`` with Auto axis types — THE mesh builder to pair with
    ``GridSpec`` / ``api.SolveOptions(grid=...)`` (the shard_map engines
    need Auto axes)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


@dataclasses.dataclass(frozen=True)
class GridSpec:
    """Static description of the process grid embedded in the mesh."""

    mesh: jax.sharding.Mesh
    row_axes: tuple[str, ...] = ("data",)
    col_axis: str = "model"

    @property
    def pr(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.row_axes]))

    @property
    def pc(self) -> int:
        return int(self.mesh.shape[self.col_axis])

    def block_spec(self) -> P:
        ra = self.row_axes[0] if len(self.row_axes) == 1 else self.row_axes
        return P(ra, self.col_axis, None)

    def block_spec_batched(self) -> P:
        """PartitionSpec for [Pr, Pc, B, cap] batched block arrays."""
        ra = self.row_axes[0] if len(self.row_axes) == 1 else self.row_axes
        return P(ra, self.col_axis, None, None)


def _int_fill(n):
    return jnp.int32(n)


def _search_depth(cap: int) -> int:
    """Alias of ``sparse.csr.window_depth`` — ONE formula for "rounds needed
    to binary-search a window of ``cap`` entries", so a plan-time pinned
    depth (api.Matcher) and the run-time measured depth can never drift."""
    return window_depth(cap)


@jax.named_scope("a2a_exchange")
def a2a_bucketed(arrays, fills, dest, valid, n_peers: int, cap_out: int,
                 axis_name, packed: bool = False):
    """Fixed-capacity bucketed all_to_all (the MPI_Alltoallv replacement).

    arrays: list of 1D [L] arrays; fills: per-array padding value.
    dest [L] in [0, n_peers); valid [L] bool. Entries beyond ``cap_out`` per
    destination bucket are dropped (counted in ``dropped`` — the caller
    retries them implicitly on the next AWAC iteration).

    ``packed=True`` (§Perf iteration M1) bitcasts all payloads into ONE
    [n_peers, cap_out, k] int32 all_to_all instead of k+1 separate
    collectives, and derives validity from the first array's fill sentinel —
    the validity exchange disappears entirely.

    Returns (out_arrays list of [n_peers*cap_out], out_valid, dropped).
    """
    L = dest.shape[0]
    d = jnp.where(valid, dest, n_peers)
    order = jnp.argsort(d, stable=True)
    ds = d[order]
    start = jnp.searchsorted(ds, jnp.arange(n_peers, dtype=ds.dtype))
    posin = jnp.arange(L, dtype=jnp.int32) - start[jnp.clip(ds, 0, n_peers - 1)].astype(jnp.int32)
    ok = (ds < n_peers) & (posin < cap_out)
    slot = jnp.where(ok, ds.astype(jnp.int32) * cap_out + posin, n_peers * cap_out)
    # explicit i32: bool sums would widen to i64 under an x64-enabled trace
    dropped = ((ds < n_peers).sum() - ok.sum()).astype(jnp.int32)

    def fill_buf(a, fv):
        buf = jnp.full((n_peers * cap_out + 1,), fv, a.dtype)
        return buf.at[slot].set(a[order])[:-1]

    if packed:
        cols = []
        for a, fv in zip(arrays, fills):
            b = fill_buf(a, fv)
            if b.dtype != jnp.int32:
                b = jax.lax.bitcast_convert_type(b, jnp.int32)
            cols.append(b)
        payload = jnp.stack(cols, axis=-1).reshape(n_peers, cap_out, len(cols))
        recv = jax.lax.all_to_all(payload, axis_name, 0, 0)
        recv = recv.reshape(-1, len(cols))
        outs = []
        for i, (a, fv) in enumerate(zip(arrays, fills)):
            col = recv[:, i]
            if a.dtype != jnp.int32:
                col = jax.lax.bitcast_convert_type(col, a.dtype)
            outs.append(col)
        # validity from the first array's sentinel (mate ids use fill = n)
        vrecv = outs[0] != fills[0]
        return outs, vrecv, dropped

    outs = []
    for a, fv in zip(arrays, fills):
        buf = fill_buf(a, fv).reshape(n_peers, cap_out)
        outs.append(jax.lax.all_to_all(buf, axis_name, 0, 0).reshape(-1))
    vbuf = jnp.zeros((n_peers * cap_out + 1,), jnp.int8).at[slot].set(
        ok.astype(jnp.int8))
    vrecv = jax.lax.all_to_all(vbuf[:-1].reshape(n_peers, cap_out),
                               axis_name, 0, 0)
    return outs, vrecv.reshape(-1).astype(bool), dropped


def _lex_pick(G, TIE, payloads, tie_fill):
    """Pick per-column (max G, tie -> min TIE) across leading device axis.

    G [D, k] float, TIE [D, k] int. Returns (g [k], tie [k], picked payloads).
    Empty columns (all -inf) return (-inf, tie_fill, payload rows from dev 0).
    """
    g0 = G.max(axis=0)
    hit = (G == g0[None, :]) & (g0[None, :] > NEG)
    tie_m = jnp.where(hit, TIE, tie_fill)
    t0 = tie_m.min(axis=0)
    hit2 = hit & (TIE == t0[None, :])
    dev = jnp.argmax(hit2, axis=0)
    out = [jnp.take_along_axis(p, dev[None, :], axis=0)[0] for p in payloads]
    return g0, t0, out


def make_dist_awac(spec: GridSpec, n: int, cap: int, a2a_caps: tuple[int, int],
                   max_iter: int = 1000, min_gain: float = MIN_GAIN,
                   packed: bool = False, backend: str = "fused",
                   window_steps: int | None = None):
    """Build the jitted distributed AWAC. Inputs: blocks [Pr, Pc, cap] (row,
    col, val) + replicated MatchState. Returns (state, iters, dropped).

    backend "fused" (default) runs the sweep engine's CSR-windowed local
    join: each block builds its per-row ``row_ptr`` once, and the Step-A
    completion lookup searches only inside row ``qi``'s short segment
    (``window_steps`` rounds ~ log2(max block-row degree), vs log2(cap) for
    the seed's global per-block lex search). "reference" keeps the seed
    path. Both are bit-identical; callers wrap the run in
    ``jax.enable_x64(True)`` to additionally collapse Step C's reductions
    into packed-key single passes.
    """
    pr, pc = spec.pr, spec.pc
    br = -(-n // pr)
    bc = -(-n // pc)
    cap1, cap2 = a2a_caps
    row_axes = spec.row_axes if len(spec.row_axes) > 1 else spec.row_axes[0]
    col_axis = spec.col_axis
    all_axes = tuple(spec.row_axes) + (spec.col_axis,)
    if window_steps is None:
        window_steps = _search_depth(cap)

    def block_fn(brow, bcol, bval, mate_row, mate_col, u, v):
        brow = brow.reshape(-1)
        bcol = bcol.reshape(-1)
        bval = bval.reshape(-1)
        b = jax.lax.axis_index(col_axis)
        a = jax.lax.axis_index(row_axes)
        if backend == "fused":
            # One-time per-block CSR row_ptr over the block's global rows
            # [a*br, (a+1)*br); the padding tail (row == n) sits beyond
            # bptr[br]. Loop-invariant, hoisted out of the AWAC rounds.
            bptr = jnp.searchsorted(
                brow, a * br + jnp.arange(br + 1, dtype=brow.dtype),
                side="left",
            ).astype(jnp.int32)

        def round_body(carry):
            state, it, _, drop_acc = carry
            mate_row, mate_col, u, v = state
            # ---- Steps A/B: relabel local nonzeros to completion-edge slots
            i2 = mate_row[bcol]
            j2 = mate_col[brow]
            valid = (brow < n) & (i2 < n) & (j2 < n)
            # stage 1: route to owning grid column (by j2)
            (o_i, o_j, o_w), v1, d1 = a2a_bucketed(
                [i2, j2, bval], [_int_fill(n), _int_fill(n), jnp.float32(0)],
                j2 // bc, valid, pc, cap1, col_axis, packed=packed,
            )
            # stage 2: route to owning grid row (by i2)
            (qi, qj, qw2), qvalid, d2 = a2a_bucketed(
                [o_i, o_j, o_w], [_int_fill(n), _int_fill(n), jnp.float32(0)],
                o_i // br, v1, pr, cap2, row_axes, packed=packed,
            )
            # ---- local join: does candidate edge (qi, qj) exist in my block?
            if backend == "fused":
                li = jnp.clip(qi - a * br, 0, br - 1)
                in_row = qvalid & (qi - a * br == li)
                lo = bptr[li]
                hi = jnp.where(in_row, bptr[li + 1], lo)
                pos, found = searchsorted_in_window(
                    bcol, qj, lo, hi, n_steps=window_steps
                )
            else:
                # (§Perf M2: search depth ceil(log2(cap)) instead of fixed 32)
                pos, found = lex_searchsorted(brow, bcol, qi, qj,
                                              n_steps=_search_depth(cap))
            w1 = bval[jnp.clip(pos, 0, brow.shape[0] - 1)]
            gain = w1 + qw2 - u[qi] - v[qj]
            cand = qvalid & found & (qi > mate_row[qj]) & (gain > min_gain)
            # ---- Step C: per-local-column winner (max gain, tie min row)
            lj = jnp.where(cand, qj - b * bc, bc).astype(jnp.int32)
            gm = jnp.where(cand, gain, NEG)
            Cg, Cidx = segment_argmax_tie(gm, qi, lj, bc + 1)
            selc = jnp.clip(Cidx[:bc], 0)
            has = Cidx[:bc] >= 0
            cg_loc = Cg[:bc]
            ci_loc = jnp.where(has, qi[selc], n).astype(jnp.int32)
            w1_loc = jnp.where(has, w1[selc], 0.0)
            w2_loc = jnp.where(has, qw2[selc], 0.0)
            # combine across grid rows
            G = jax.lax.all_gather(cg_loc, row_axes)
            I = jax.lax.all_gather(ci_loc, row_axes)
            W1 = jax.lax.all_gather(w1_loc, row_axes)
            W2 = jax.lax.all_gather(w2_loc, row_axes)
            g0, i0, (w1_0, w2_0) = _lex_pick(G, I, [W1, W2], jnp.int32(n))
            # ---- replicate per-column winners globally (Step C output)
            Cgain = jax.lax.all_gather(g0, col_axis).reshape(-1)[:n]
            Ci = jax.lax.all_gather(i0, col_axis).reshape(-1)[:n]
            Cw1 = jax.lax.all_gather(w1_0, col_axis).reshape(-1)[:n]
            Cw2 = jax.lax.all_gather(w2_0, col_axis).reshape(-1)[:n]
            Ci = jnp.where(Cgain > NEG, Ci, n).astype(jnp.int32)
            # ---- Step D + augmentation: replicated, shared with single-device
            state, n_surv = single.select_and_augment(
                n, Cgain, Ci, Cw1, Cw2, state, min_gain
            )
            return state, it + 1, n_surv > 0, drop_acc + d1 + d2

        def cond(carry):
            _, it, go, _ = carry
            return go & (it < max_iter)

        state0 = MatchState(mate_row, mate_col, u, v)
        state, iters, _, dropped = jax.lax.while_loop(
            cond, round_body, (state0, jnp.array(0, jnp.int32), jnp.array(True),
                               jnp.array(0, jnp.int32))
        )
        dropped = jax.lax.psum(dropped, all_axes)
        return state.mate_row, state.mate_col, state.u, state.v, iters, dropped

    blk = spec.block_spec()
    fn = _shard_map(
        block_fn,
        mesh=spec.mesh,
        in_specs=(blk, blk, blk, P(), P(), P(), P()),
        out_specs=(P(), P(), P(), P(), P(), P()),
    )

    @jax.jit
    def run(brow, bcol, bval, state: MatchState):
        mr, mc, u, v, iters, dropped = fn(
            brow, bcol, bval, state.mate_row, state.mate_col, state.u, state.v
        )
        return MatchState(mr, mc, u, v), iters, dropped

    return run


def make_dist_greedy_maximal(spec: GridSpec, n: int, cap: int, max_rounds: int = 0):
    """Distributed greedy weighted maximal matching (proposal rounds).
    Bit-identical to repro.core.single.greedy_maximal."""
    pr, pc = spec.pr, spec.pc
    bc = -(-n // pc)
    row_axes = spec.row_axes if len(spec.row_axes) > 1 else spec.row_axes[0]
    col_axis = spec.col_axis
    jvec = jnp.arange(n, dtype=jnp.int32)
    ivec = jnp.arange(n, dtype=jnp.int32)

    def block_fn(brow, bcol, bval, mate_row, mate_col):
        brow = brow.reshape(-1)
        bcol = bcol.reshape(-1)
        bval = bval.reshape(-1)
        b = jax.lax.axis_index(col_axis)

        def round_body(carry):
            mate_row, mate_col, _ = carry
            avail = (brow < n) & (mate_col[brow] == n) & (mate_row[bcol] == n)
            lj = jnp.where(avail, bcol - b * bc, bc).astype(jnp.int32)
            score = jnp.where(avail, bval, NEG)
            Pg, Pidx = segment_argmax_tie(score, brow, lj, bc + 1)
            sel = jnp.clip(Pidx[:bc], 0)
            has = Pidx[:bc] >= 0
            pg_loc = Pg[:bc]
            pi_loc = jnp.where(has, brow[sel], n).astype(jnp.int32)
            G = jax.lax.all_gather(pg_loc, row_axes)
            I = jax.lax.all_gather(pi_loc, row_axes)
            g0, i0, _ = _lex_pick(G, I, [], jnp.int32(n))
            prop_val = jax.lax.all_gather(g0, col_axis).reshape(-1)[:n]
            prop_row = jax.lax.all_gather(i0, col_axis).reshape(-1)[:n]
            prop_row = jnp.where(prop_val > NEG, prop_row, n).astype(jnp.int32)
            # replicated per-row contest (same as single-device round)
            pv = jnp.where(prop_row < n, prop_val, NEG)
            _, rj = segment_max_with_payload(pv, jvec, prop_row, n + 1)
            ok = rj[:n] >= 0
            wcol = jnp.where(ok, rj[:n], n).astype(jnp.int32)
            mate_col = mate_col.at[jnp.where(ok, ivec, n)].set(wcol)
            mate_row = mate_row.at[wcol].set(jnp.where(ok, ivec, n).astype(jnp.int32))
            mate_col = mate_col.at[n].set(n)
            mate_row = mate_row.at[n].set(n)
            return mate_row, mate_col, ok.any()

        mate_row, mate_col, _ = jax.lax.while_loop(
            lambda c: c[2], round_body, (mate_row, mate_col, jnp.array(True))
        )
        return mate_row, mate_col

    blk = spec.block_spec()
    fn = _shard_map(
        block_fn, mesh=spec.mesh,
        in_specs=(blk, blk, blk, P(), P()),
        out_specs=(P(), P()),
    )

    @jax.jit
    def run(brow, bcol, bval):
        n_ = n
        mr0 = jnp.full((n_ + 1,), n_, jnp.int32)
        mc0 = jnp.full((n_ + 1,), n_, jnp.int32)
        return fn(brow, bcol, bval, mr0, mc0)

    return run


def make_dist_mcm(spec: GridSpec, n: int, cap: int):
    """Distributed maximum cardinality matching: layered BFS with per-row
    parent selection across the grid, replicated trace/flip (shared with the
    single-device implementation). Bit-identical to repro.core.single.mcm."""
    pr, pc = spec.pr, spec.pc
    br = -(-n // pr)
    row_axes = spec.row_axes if len(spec.row_axes) > 1 else spec.row_axes[0]
    col_axis = spec.col_axis

    def block_fn(brow, bcol, bval, mate_row, mate_col):
        brow = brow.reshape(-1)
        bcol = bcol.reshape(-1)
        bval = bval.reshape(-1)
        a = jax.lax.axis_index(spec.row_axes if len(spec.row_axes) > 1
                               else spec.row_axes[0])

        def bfs(mate_row, mate_col):
            frontier = jnp.zeros((n + 1,), bool).at[:n].set(mate_row[:n] == n)
            parent_col = jnp.full((n + 1,), n, jnp.int32)
            visited = jnp.zeros((n + 1,), bool)

            def bfs_body(carry):
                frontier, parent_col, visited, found, layers, _ = carry
                elig = (brow < n) & frontier[bcol] & (~visited[brow])
                li = jnp.where(elig, brow - a * br, br).astype(jnp.int32)
                score = jnp.where(elig, bval, NEG)
                Rg, Ridx = segment_argmax_tie(score, bcol, li, br + 1)
                sel = jnp.clip(Ridx[:br], 0)
                has = Ridx[:br] >= 0
                rg_loc = Rg[:br]
                rc_loc = jnp.where(has, bcol[sel], n).astype(jnp.int32)
                # combine across grid columns (a row's edges live in one grid
                # row, spread over all grid columns)
                G = jax.lax.all_gather(rg_loc, col_axis)
                C = jax.lax.all_gather(rc_loc, col_axis)
                g0, c0, _ = _lex_pick(G, C, [], jnp.int32(n))
                # replicate across grid rows -> global per-row parent
                pval = jax.lax.all_gather(g0, row_axes).reshape(-1)[:n]
                pcol = jax.lax.all_gather(c0, row_axes).reshape(-1)[:n]
                new = (pval > NEG) & (~visited[:n])
                pc_new = jnp.where(new, pcol, parent_col[:n]).astype(jnp.int32)
                parent_col = parent_col.at[:n].set(pc_new)
                visited = visited.at[:n].set(visited[:n] | new)
                free_new = new & (mate_col[:n] == n)
                found = free_new.any()
                nf_idx = jnp.where(new & ~free_new, mate_col[:n], n)
                frontier = (jnp.zeros((n + 1,), bool).at[nf_idx].set(True)
                            .at[n].set(False))
                return frontier, parent_col, visited, found, layers + 1, new.any()

            def bfs_cond(carry):
                _, _, _, found, layers, progressed = carry
                return (~found) & progressed & (layers <= n)

            return jax.lax.while_loop(
                bfs_cond, bfs_body,
                (frontier, parent_col, visited, jnp.array(False),
                 jnp.array(0, jnp.int32), jnp.array(True)),
            )

        def phase_body(carry):
            mate_row, mate_col, _ = carry
            frontier, parent_col, visited, found, layers, _ = bfs(mate_row, mate_col)
            mate_row, mate_col = single.trace_and_flip(
                parent_col, visited, found, layers, mate_row, mate_col, n
            )
            return mate_row, mate_col, found

        def phase_cond(carry):
            mate_row, _, go = carry
            return go & (mate_row[:n] == n).any()

        mate_row, mate_col, _ = jax.lax.while_loop(
            phase_cond, phase_body, (mate_row, mate_col, jnp.array(True))
        )
        return mate_row, mate_col

    blk = spec.block_spec()
    fn = _shard_map(
        block_fn, mesh=spec.mesh,
        in_specs=(blk, blk, blk, P(), P()),
        out_specs=(P(), P()),
    )

    @jax.jit
    def run(brow, bcol, bval, mate_row, mate_col):
        return fn(brow, bcol, bval, mate_row, mate_col)

    return run


# --------------------------------------------------------------------------
# Host-level driver
# --------------------------------------------------------------------------


@dataclasses.dataclass
class DistAWPM:
    """Deprecated three-dispatch distributed driver — use
    ``repro.core.api.solve`` / ``plan`` (which route through the
    single-dispatch distributed-batched engine). Partitions the graph,
    builds the three jitted phases, runs them in sequence."""

    spec: GridSpec
    n: int
    cap: int
    a2a_caps: tuple[int, int]
    max_iter: int = 1000
    min_gain: float = MIN_GAIN
    packed: bool = False
    backend: str = "fused"

    def __post_init__(self):
        warn_legacy("repro.core.dist.DistAWPM", "solve()/plan()",
                    stacklevel=4)
        self._greedy = make_dist_greedy_maximal(self.spec, self.n, self.cap)
        self._mcm = make_dist_mcm(self.spec, self.n, self.cap)
        self._awac_cache = {}

    def _get_awac(self, window_steps: int | None):
        key = window_steps
        if key not in self._awac_cache:
            self._awac_cache[key] = make_dist_awac(
                self.spec, self.n, self.cap, self.a2a_caps, self.max_iter,
                self.min_gain, packed=self.packed, backend=self.backend,
                window_steps=window_steps,
            )
        return self._awac_cache[key]

    def partition(self, g):
        """BipartiteGraph -> device-sharded block arrays (plus the static
        windowed-search depth measured from the partition's block rows)."""
        m = np.arange(g.capacity) < g.nnz
        part = partition_coo_2d(
            g.row[m], g.col[m], g.val[m], self.n, self.spec.pr, self.spec.pc,
            cap=self.cap,
        )
        sharding = jax.sharding.NamedSharding(self.spec.mesh, self.spec.block_spec())
        brow = jax.device_put(part.row, sharding)
        bcol = jax.device_put(part.col, sharding)
        bval = jax.device_put(part.val, sharding)
        # max nonzeros any (block, row) pair holds -> windowed search depth
        rows = part.row.reshape(part.row.shape[0] * part.row.shape[1], -1)
        widest = max(max_row_nnz(blk_rows, self.n) for blk_rows in rows)
        return brow, bcol, bval, window_depth(widest)

    def run(self, g, state: MatchState | None = None):
        """Returns (state, awac_iters, dropped)."""
        brow, bcol, bval, ws = self.partition(g)
        if state is None:
            mr, mc = self._greedy(brow, bcol, bval)
            mr, mc = self._mcm(brow, bcol, bval, mr, mc)
            # u, v from mates (cheap replicated lookup on host path)
            row = jnp.asarray(g.row)
            col = jnp.asarray(g.col)
            val = jnp.asarray(g.val)
            state = single.state_from_mates(row, col, val, self.n, mr, mc)
        awac = self._get_awac(ws if self.backend == "fused" else None)
        if self.backend == "fused":
            # Packed-key single-pass Step C reductions (repro.sparse.ops)
            with jax.enable_x64(True):
                return awac(brow, bcol, bval, state)
        return awac(brow, bcol, bval, state)


def default_caps(n: int, m: int, pr: int, pc: int, slack: float = 2.0):
    """Bucket capacities for the two routing stages: expected load x slack.
    Under the paper's i.i.d. assumption each process receives O(m/p) requests."""
    cap_block = max(int(slack * m / (pr * pc)) + 16, 32)
    cap1 = max(int(slack * cap_block / pc) + 16, 16)
    cap2 = max(int(slack * cap1 * pc / pr) + 16, 16)
    return cap1, cap2


# --------------------------------------------------------------------------
# Distributed-BATCHED engine: B instances, one shard_map dispatch (§5)
# --------------------------------------------------------------------------


class ExchangeIntegrityError(RuntimeError):
    """The two-stage bucketed exchange lost, duplicated, or corrupted
    payloads: the result would not be bit-identical to the local engines.
    Raised by ``api._solve_dist`` on a non-zero dropped counter (undersized
    user a2a_caps) or a failed ``SolveOptions(exchange_check=True)``
    conservation audit."""


# Trace-time exchange hook for the chaos harness (``runtime.chaos``): when
# set, called as ``tap(axis_name, outs, valid) -> (outs, valid)`` on every
# batched exchange's received buffers (axis_name distinguishes the two
# routing stages). None in production — the branch folds away at trace time.
_EXCHANGE_TAP = None


def _tapped(axis_name, outs, valid):
    if _EXCHANGE_TAP is None:
        return outs, valid
    return _EXCHANGE_TAP(axis_name, outs, valid)


def _conserved(arrays, valid):
    """Order-independent conservation signature of an exchange payload:
    (count of valid entries, int32-wraparound checksum of the valid
    payloads' raw bits). The two-stage exchange is a pure routing of
    (i, j, w) triples, so both quantities are conserved end-to-end when
    nothing is dropped — any drop/duplicate changes the count, any
    corruption (including injected NaNs) changes the checksum."""
    cnt = valid.astype(jnp.int32).sum().astype(jnp.int32)
    chk = jnp.zeros((), jnp.int32)
    for a in arrays:
        bits = a if a.dtype == jnp.int32 \
            else jax.lax.bitcast_convert_type(a, jnp.int32)
        chk = chk + jnp.where(valid, bits, 0).sum().astype(jnp.int32)
    return cnt, chk


@jax.named_scope("a2a_exchange")
def a2a_bucketed_batched(arrays, fills, dest, valid, n_peers: int,
                         cap_out: int, axis_name, packed: bool = False):
    """Batched ``a2a_bucketed``: arrays/dest/valid are [B, L] and ONE
    collective per payload (one total when ``packed``) carries every
    instance's buckets as [n_peers, B, cap_out(, k)] — per-message latency
    amortizes across the whole batch instead of paying B exchanges.

    Returns (out arrays list of [B, n_peers * cap_out], out_valid, dropped,
    filled): ``dropped`` the valid entries no bucket had room for and
    ``filled`` the slots holding a valid entry, int32 scalars summed over
    instances. Each call carries ``B * n_peers * cap_out`` slots, of
    ``a2a_slot_bytes(len(arrays), packed)`` bytes each."""
    b, L = dest.shape
    bix = jnp.arange(b, dtype=jnp.int32)[:, None]
    d = jnp.where(valid, dest, n_peers)
    order = jnp.argsort(d, axis=1, stable=True)
    ds = jnp.take_along_axis(d, order, axis=1)
    peers = jnp.arange(n_peers, dtype=ds.dtype)
    start = jax.vmap(lambda s: jnp.searchsorted(s, peers))(ds)
    posin = jnp.arange(L, dtype=jnp.int32)[None, :] - jnp.take_along_axis(
        start, jnp.clip(ds, 0, n_peers - 1).astype(jnp.int32), axis=1
    ).astype(jnp.int32)
    ok = (ds < n_peers) & (posin < cap_out)
    slot = jnp.where(ok, ds.astype(jnp.int32) * cap_out + posin,
                     n_peers * cap_out)
    filled = ok.sum(dtype=jnp.int32)
    dropped = (ds < n_peers).sum(dtype=jnp.int32) - filled

    def fill_buf(a, fv):
        src = jnp.take_along_axis(a, order, axis=1)
        buf = jnp.full((b, n_peers * cap_out + 1), fv, a.dtype)
        return buf.at[bix, slot].set(src)[:, :-1]

    def exchange(x):
        shp = x.shape
        x = x.reshape(b, n_peers, cap_out, *shp[2:])
        x = jnp.moveaxis(x, 1, 0)  # [n_peers, B, cap_out, ...]
        x = jax.lax.all_to_all(x, axis_name, 0, 0)
        return jnp.moveaxis(x, 0, 1).reshape(shp)

    if packed:
        cols = []
        for a, fv in zip(arrays, fills):
            bf = fill_buf(a, fv)
            if bf.dtype != jnp.int32:
                bf = jax.lax.bitcast_convert_type(bf, jnp.int32)
            cols.append(bf)
        recv = exchange(jnp.stack(cols, axis=-1))
        outs = []
        for i, (a, fv) in enumerate(zip(arrays, fills)):
            c = recv[..., i]
            if a.dtype != jnp.int32:
                c = jax.lax.bitcast_convert_type(c, a.dtype)
            outs.append(c)
        # validity from the first array's sentinel (mate ids use fill = n)
        outs, vrecv = _tapped(axis_name, outs, outs[0] != fills[0])
        return outs, vrecv, dropped, filled

    outs = [exchange(fill_buf(a, fv)) for a, fv in zip(arrays, fills)]
    vbuf = jnp.zeros((b, n_peers * cap_out + 1), jnp.int8).at[bix, slot].set(
        ok.astype(jnp.int8))[:, :-1]
    outs, vrecv = _tapped(axis_name, outs, exchange(vbuf).astype(bool))
    return outs, vrecv, dropped, filled


def a2a_slot_bytes(k: int, packed: bool) -> int:
    """Bytes one bucket slot of ``a2a_bucketed_batched`` carries: ``k``
    4-byte payloads, plus the int8 validity buffer on the unpacked path."""
    return 4 * k + (0 if packed else 1)


def safe_a2a_caps(cap_blk: int, pr: int, pc: int) -> tuple[int, int]:
    """Bucket capacities making the two-stage exchange provably drop-free:
    stage 1 can at worst route every local edge to one column peer
    (cap1 = cap_blk); stage 2 at worst forwards everything it received to
    one row peer (cap2 = pc * cap1). The bit-identity contract with
    ``core.batch.awpm_batched`` requires that no candidate is ever dropped,
    so these are the driver defaults."""
    return cap_blk, pc * cap_blk


DIST_BATCHED_BACKENDS = ("fused", "reference", "xla")


@functools.lru_cache(maxsize=None)
def _make_awpm_dist_batched(spec: GridSpec, n: int, b: int, cap: int,
                            a2a_caps: tuple[int, int], max_iter: int = 1000,
                            min_gain: float = MIN_GAIN, packed: bool = False,
                            backend: str = "fused",
                            window_steps: int | None = None,
                            from_state: bool = False,
                            degrade_infeasible: bool = False,
                            exchange_check: bool = False):
    """Build the single-dispatch distributed-batched AWPM (DESIGN.md §5).

    One shard_map dispatch runs greedy maximal -> MCM -> dual build -> AWAC
    for all B instances: the batched engine's loop skeletons
    (``core.batch.greedy_loop`` / ``mcm_loop`` / ``awac_loop``) carry the
    per-instance convergence masks, and only the per-round winner
    computations are swapped for 2D-block reductions + collectives — so the
    result is bit-identical per instance to ``core.batch.awpm_batched`` by
    construction. Edge state is sharded [Pr, Pc, B, cap]; all O(n) matching
    state is replicated [B, n + 1].

    backend: "fused" (default) joins Step A/B candidates against the local
    block through the batched CSR-windowed search (the fused sweep
    substrate, sparse/ops.py); "reference" keeps the per-block global lex
    search. On the 1x1 grid, "xla" routes Steps A+B+C through
    ``core.batch``'s fused batched sweep directly — the block is the whole
    instance, so no exchange is needed.

    Returns jitted ``run(brow, bcol, bval) -> (MatchState, iters [B],
    dropped, counters)`` over [Pr, Pc, B, cap] blocks, the counters those
    of the batched skeletons plus the AWAC exchange's (``telemetry``):
    ``a2a_slots``, ``a2a_filled`` and ``a2a_bytes``, int64 [Pr * Pc], one
    entry per device, summed over both stages and every AWAC round (the
    x64 context is what gives them 64 bits). With ``from_state=True`` the
    runner instead takes a replicated initial MatchState ([B, n + 1]
    fields) and runs the AWAC phase only — ``run(brow, bcol, bval,
    mate_row, mate_col, u, v)`` — the distributed analogue of
    ``core.batch.awac_batched``.
    """
    pr, pc = spec.pr, spec.pc
    if backend not in DIST_BATCHED_BACKENDS:
        raise ValueError(f"unknown dist AWAC backend {backend!r}")
    if backend == "xla" and (pr, pc) != (1, 1):
        raise ValueError(
            f"backend {backend!r} routes through core.batch's local sweep "
            f"and needs the 1x1 grid, got {pr}x{pc}")
    br = -(-n // pr)
    bc = -(-n // pc)
    cap1, cap2 = a2a_caps
    row_axes = spec.row_axes if len(spec.row_axes) > 1 else spec.row_axes[0]
    col_axis = spec.col_axis
    all_axes = tuple(spec.row_axes) + (spec.col_axis,)
    if window_steps is None:
        window_steps = _search_depth(cap)
    # MCM's sorted-segment scan levels, from the same measured depth as
    # ``single._scan_levels``: a block's row holds at most min(cap, bc)
    scan_levels = min(window_steps, window_depth(min(cap, bc))) - 1
    # bucket slots one device's two all-to-alls carry each AWAC round (its
    # own bucket included), and the bytes of a slot: i, j and w
    round_slots = b * (pc * cap1 + pr * cap2)
    slot_bytes = a2a_slot_bytes(3, packed)

    def block_fn(brow, bcol, bval, *state_args):
        brow = brow.reshape(b, cap)
        bcol = bcol.reshape(b, cap)
        bval = bval.reshape(b, cap)
        adev = jax.lax.axis_index(row_axes)
        bdev = jax.lax.axis_index(col_axis)
        # Per-instance CSR row_ptr over this device's global rows
        # [adev*br, (adev+1)*br); the padding tail sits beyond bptr[:, br].
        # Loop-invariant, hoisted out of every phase loop.
        targets = adev * br + jnp.arange(br + 1, dtype=brow.dtype)
        bptr = jax.vmap(
            lambda r: jnp.searchsorted(r, targets, side="left"))(brow
        ).astype(jnp.int32)
        # this device's global rows, clipped to n (never visited, no edges)
        grows = jnp.broadcast_to(
            jnp.clip(adev * br + jnp.arange(br, dtype=jnp.int32), 0, n)[None],
            (b, br))

        def gather_n(x, axis):
            """all_gather [B, k] along ``axis`` -> replicated [B, n]
            (device-major concat, then the padded tail sliced off)."""
            g = jax.lax.all_gather(x, axis)
            return jnp.moveaxis(g, 0, 1).reshape(b, -1)[:, :n]

        # ---- greedy phase: per-column proposals from 2D blocks ----
        def greedy_propose(mate_row, mate_col):
            avail = (brow < n) \
                & (jnp.take_along_axis(mate_col, brow, axis=1) == n) \
                & (jnp.take_along_axis(mate_row, bcol, axis=1) == n)
            lj = jnp.where(avail, bcol - bdev * bc, bc).astype(jnp.int32)
            score = jnp.where(avail, bval, NEG)
            Pg, Pidx = batched_segment_argmax_tie(score, brow, lj, bc + 1)
            sel = jnp.clip(Pidx[:, :bc], 0)
            has = Pidx[:, :bc] >= 0
            pi_loc = jnp.where(
                has, jnp.take_along_axis(brow, sel, axis=1), n
            ).astype(jnp.int32)
            G = jax.lax.all_gather(Pg[:, :bc], row_axes)
            I = jax.lax.all_gather(pi_loc, row_axes)
            g0, i0, _ = _lex_pick(G, I, [], jnp.int32(n))
            pv = gather_n(g0, col_axis)
            prow = gather_n(i0, col_axis)
            return pv, jnp.where(pv > NEG, prow, n).astype(jnp.int32)

        # ---- MCM phase: per-row BFS parents from 2D blocks ----
        def mcm_parents(frontier, visited):
            # each local row's edges are one run [bptr[r], bptr[r + 1]),
            # sorted by column, so the smallest column among the heaviest
            # is the scatter reductions' tie-break; a visited row keeps its
            # parent, so it is dropped after the scan, not edge by edge
            score = jnp.where(
                (brow < n) & jnp.take_along_axis(frontier, bcol, axis=1),
                bval, NEG)
            Rg, best = batched_sorted_segment_max_with_payload(
                score, bcol, brow, bptr, scan_levels)
            new = (best >= 0) & ~jnp.take_along_axis(visited, grows, axis=1)
            Rg = jnp.where(new, Rg, NEG)
            rc_loc = jnp.where(new, best, n).astype(jnp.int32)
            # a row's edges live in ONE grid row, spread over grid columns
            G = jax.lax.all_gather(Rg, col_axis)
            C = jax.lax.all_gather(rc_loc, col_axis)
            g0, c0, _ = _lex_pick(G, C, [], jnp.int32(n))
            pval = gather_n(g0, row_axes)
            pcol = gather_n(c0, row_axes)
            return pval > NEG, pcol

        # ---- dual build: u, v from the mates (windowed block lookup) ----
        def uv_state(mate_row, mate_col):
            q = jnp.take_along_axis(mate_col, grows, axis=1)
            pos, found = batched_searchsorted_in_window(
                bcol, q, bptr[:, :br], bptr[:, 1:], n_steps=window_steps)
            w = jnp.where(
                found & (grows < n),
                jnp.take_along_axis(bval, jnp.clip(pos, 0, cap - 1), axis=1),
                0.0)
            bix = jnp.arange(b, dtype=jnp.int32)[:, None]
            # each matched edge (i, mate_col[i]) lives in exactly one block,
            # so the psum replicates the one found weight (plus exact zeros)
            uu = jnp.zeros((b, n + 1), jnp.float32).at[bix, grows].set(w)
            u = jax.lax.psum(uu, all_axes).at[:, n].set(0.0)
            v = jnp.zeros((b, n + 1), jnp.float32).at[:, :n].set(
                jnp.where(mate_row[:, :n] < n,
                          jnp.take_along_axis(
                              u, jnp.clip(mate_row[:, :n], 0, n), axis=1),
                          0.0))
            return MatchState(mate_row, mate_col, u, v)

        # ---- AWAC Steps A+B+C: batched exchange + windowed local join ----
        def cwinners(state):
            mate_row, mate_col, u, v = state
            i2 = jnp.take_along_axis(mate_row, bcol, axis=1)
            j2 = jnp.take_along_axis(mate_col, brow, axis=1)
            valid = (brow < n) & (i2 < n) & (j2 < n)
            if exchange_check:
                cnt_in, chk_in = _conserved([i2, j2, bval], valid)
            # stage 1: route to owning grid column (by j2)
            (o_i, o_j, o_w), v1, d1, f1 = a2a_bucketed_batched(
                [i2, j2, bval],
                [_int_fill(n), _int_fill(n), jnp.float32(0)],
                j2 // bc, valid, pc, cap1, col_axis, packed=packed,
            )
            # stage 2: route to owning grid row (by o_i)
            (qi, qj, qw2), qvalid, d2, f2 = a2a_bucketed_batched(
                [o_i, o_j, o_w],
                [_int_fill(n), _int_fill(n), jnp.float32(0)],
                o_i // br, v1, pr, cap2, row_axes, packed=packed,
            )
            if exchange_check:
                # end-to-end conservation: the exchange is a pure routing
                # of (i, j, w) triples, so a global count balance (minus
                # capacity drops) and an order-independent checksum (when
                # drop-free) must both hold every round
                cnt_out, chk_out = _conserved([qi, qj, qw2], qvalid)
                tot = jax.lax.psum(
                    jnp.stack([cnt_in, chk_in, cnt_out, chk_out, d1 + d2]),
                    all_axes)
                bad = ((tot[0] - tot[4]) != tot[2]) \
                    | ((tot[4] == 0) & (tot[1] != tot[3]))
                aux = jnp.stack([tot[4], bad.astype(jnp.int32)])
            else:
                aux = d1 + d2
            if backend == "reference":
                pos, found = jax.vmap(functools.partial(
                    lex_searchsorted, n_steps=_search_depth(cap)
                ))(brow, bcol, qi, qj)
            else:  # fused sweep substrate: batched CSR-windowed search
                li = jnp.clip(qi - adev * br, 0, br - 1)
                in_row = qvalid & (qi - adev * br == li)
                lo = jnp.take_along_axis(bptr, li, axis=1)
                hi = jnp.where(
                    in_row, jnp.take_along_axis(bptr, li + 1, axis=1), lo)
                pos, found = batched_searchsorted_in_window(
                    bcol, qj, lo, hi, n_steps=window_steps)
            w1 = jnp.take_along_axis(bval, jnp.clip(pos, 0, cap - 1), axis=1)
            gain = w1 + qw2 \
                - jnp.take_along_axis(u, jnp.clip(qi, 0, n), axis=1) \
                - jnp.take_along_axis(v, jnp.clip(qj, 0, n), axis=1)
            cand = qvalid & found & (gain > min_gain) & (
                qi > jnp.take_along_axis(mate_row, jnp.clip(qj, 0, n), axis=1))
            # Step C: per-local-column winner (max gain, tie min row)
            lj = jnp.where(cand, qj - bdev * bc, bc).astype(jnp.int32)
            gm = jnp.where(cand, gain, NEG)
            Cg, Cidx = batched_segment_argmax_tie(gm, qi, lj, bc + 1)
            sel = jnp.clip(Cidx[:, :bc], 0)
            has = Cidx[:, :bc] >= 0
            ci_loc = jnp.where(
                has, jnp.take_along_axis(qi, sel, axis=1), n
            ).astype(jnp.int32)
            w1_loc = jnp.where(has, jnp.take_along_axis(w1, sel, axis=1), 0.0)
            w2_loc = jnp.where(has, jnp.take_along_axis(qw2, sel, axis=1), 0.0)
            G = jax.lax.all_gather(Cg[:, :bc], row_axes)
            I = jax.lax.all_gather(ci_loc, row_axes)
            W1 = jax.lax.all_gather(w1_loc, row_axes)
            W2 = jax.lax.all_gather(w2_loc, row_axes)
            g0, i0, (w1_0, w2_0) = _lex_pick(G, I, [W1, W2], jnp.int32(n))
            Cgain = gather_n(g0, col_axis)
            Ci = gather_n(i0, col_axis)
            Cw1 = gather_n(w1_0, col_axis)
            Cw2 = gather_n(w2_0, col_axis)
            Ci = jnp.where(Cgain > NEG, Ci, n).astype(jnp.int32)
            moved = jnp.stack([jnp.int64(round_slots),
                               (f1 + f2).astype(jnp.int64)])
            return Cgain, Ci, Cw1, Cw2, (aux, moved)

        if backend == "xla":
            # 1x1 grid: the block IS the instance — Steps A+B+C run through
            # the batched fused sweep.
            rptr = jax.vmap(lambda r: jnp.searchsorted(
                r, jnp.arange(n + 2, dtype=r.dtype), side="left"))(brow
            ).astype(jnp.int32)

            def cwinners(state):  # noqa: F811 — intentional override
                out = batch._cwinners_batched(
                    backend, brow, bcol, bval, rptr, n, state, min_gain,
                    window_steps)
                zero = jnp.zeros((2,), jnp.int32) if exchange_check \
                    else jnp.array(0, jnp.int32)
                return (*out, (zero, jnp.zeros((2,), jnp.int64)))

        # ---- the pipeline: shared batched loop skeletons, dist winners ----
        counters = {}
        if from_state:
            state0 = MatchState(*state_args)
        else:
            mr, mc, greedy_counts = batch.greedy_loop(n, b, greedy_propose)
            mr, mc, mcm_counts = batch.mcm_loop(n, b, mr, mc, mcm_parents,
                                                sorted_scan=True)
            counters = {**greedy_counts, **mcm_counts}
            state0 = uv_state(mr, mc)
        state, iters, (aux, moved), awac_counts = batch.awac_loop(
            n, state0, max_iter, min_gain, cwinners,
            active0=(batch.is_perfect_batched(state0, n)
                     if degrade_infeasible else None),
            aux0=(jnp.zeros((2,), jnp.int32) if exchange_check
                  else jnp.array(0, jnp.int32), jnp.zeros((2,), jnp.int64)))
        if not exchange_check:
            # the per-round [dropped, integrity] pair is already psum'd
            # inside cwinners; the plain dropped counter is not
            aux = jax.lax.psum(aux, all_axes)
        # this device's exchange, in 64 bits: the host sums the devices
        exchange = {"a2a_slots": moved[:1], "a2a_filled": moved[1:],
                    "a2a_bytes": moved[:1] * slot_bytes}
        return (state.mate_row, state.mate_col, state.u, state.v, iters,
                aux, {**counters, **awac_counts}, exchange)

    blk = spec.block_spec_batched()
    state_specs = (P(), P(), P(), P()) if from_state else ()
    fn = _shard_map(
        block_fn, mesh=spec.mesh,
        in_specs=(blk, blk, blk) + state_specs,
        out_specs=(P(), P(), P(), P(), P(), P(), P(), P(all_axes)),
    )

    @jax.jit
    def run(brow, bcol, bval, *state_args):
        mr, mc, u, v, iters, dropped, counters, exchange = fn(
            brow, bcol, bval, *state_args)
        return MatchState(mr, mc, u, v), iters, dropped, {**counters,
                                                          **exchange}

    return run


@dataclasses.dataclass
class _DistBatchedAWPM:
    """Host driver for the single-dispatch distributed-batched AWPM: plans
    the per-block capacity from true block occupancy, partitions the padded
    [B, cap] batch over the grid, plans drop-free a2a bucket capacities,
    and dispatches the cached engine. Internal engine behind
    ``repro.core.api.solve``/``plan`` (grid dispatch target) and the
    deprecated ``DistBatchedAWPM`` / ``awpm_dist_batched`` shims."""

    spec: GridSpec
    n: int
    cap: int | None = None  # per-block capacity (None -> true occupancy)
    a2a_caps: tuple[int, int] | None = None  # None -> safe_a2a_caps
    max_iter: int = 1000
    min_gain: float = MIN_GAIN
    packed: bool = False
    backend: str = "fused"
    window_steps: int | None = None  # None -> measured from the partition
    degrade_infeasible: bool = False  # skip AWAC on infeasible instances
    exchange_check: bool = False  # per-round exchange conservation audit

    def partition(self, row, col, val):
        """[B, cap] padded COO -> device-sharded [Pr, Pc, B, cap_blk] blocks
        (plus the partition and the measured windowed-search depth)."""
        with telemetry.span("repro.partition"):
            part = partition_coo_2d_batched(
                row, col, val, self.n, self.spec.pr, self.spec.pc,
                cap=self.cap)
        sharding = jax.sharding.NamedSharding(
            self.spec.mesh, self.spec.block_spec_batched())
        brow, bcol, bval = telemetry.call(
            "repro.device_put", jax.device_put, (part.row, part.col, part.val),
            sharding)
        with telemetry.span("repro.window_depth"):
            ws = window_depth(max_row_nnz(part.row.reshape(-1, part.cap),
                                          self.n))
        return part, brow, bcol, bval, ws

    def run(self, row, col, val, state: MatchState | None = None):
        """row/col/val: padded [B, cap] lex-sorted COO sharing n (see
        ``core.batch.stack_graphs``). Returns (MatchState with [B, n + 1]
        fields, awac_iters [B], dropped) — per instance bit-identical to
        ``core.batch.awpm_batched(row, col, val, n)``; the engine's counters
        are added to the solve in progress. An explicit replicated
        ``state`` skips greedy/MCM and runs the AWAC phase only (the
        distributed ``core.batch.awac_batched``)."""
        part, brow, bcol, bval, ws = self.partition(row, col, val)
        caps = self.a2a_caps or safe_a2a_caps(
            part.cap, self.spec.pr, self.spec.pc)
        if self.window_steps is not None:
            # explicit pin (api.plan): extra search depth never changes a
            # windowed-search result, so any depth >= the measured one is
            # bit-identical — and a pinned depth keys one compiled engine
            # across run() calls with varying data. Clamped UP to the
            # measured need so an undersized pin can never silently miss
            # completion edges.
            ws = max(ws, self.window_steps)
        fn = _make_awpm_dist_batched(
            self.spec, self.n, part.b, part.cap, caps, self.max_iter,
            self.min_gain, packed=self.packed, backend=self.backend,
            window_steps=ws, from_state=state is not None,
            degrade_infeasible=self.degrade_infeasible,
            exchange_check=self.exchange_check)
        # x64 trace context: every winner reduction collapses to the
        # packed-key single pass (repro.sparse.ops), as in core.batch.
        with jax.enable_x64(True):
            state, iters, dropped, counters = fn(
                brow, bcol, bval, *(() if state is None else state))
        telemetry.count(counters)
        return state, iters, dropped


@dataclasses.dataclass
class DistBatchedAWPM(_DistBatchedAWPM):
    """Deprecated host driver — use ``repro.core.api.solve`` (one-shot) or
    ``repro.core.api.plan`` (compile-once/run-many ``Matcher``)."""

    def __post_init__(self):
        warn_legacy("repro.core.dist.DistBatchedAWPM", "plan()",
                    stacklevel=4)


def make_awpm_dist_batched(spec: GridSpec, n: int, b: int, cap: int,
                           a2a_caps: tuple[int, int], max_iter: int = 1000,
                           min_gain: float = MIN_GAIN, packed: bool = False,
                           backend: str = "fused",
                           window_steps: int | None = None,
                           from_state: bool = False):
    """Deprecated factory for the raw block-level engine — use
    ``repro.core.api.plan`` (the ``Matcher`` handle pins capacities and the
    compiled engine at plan time)."""
    warn_legacy("repro.core.dist.make_awpm_dist_batched", "plan()")
    engine = _make_awpm_dist_batched(
        spec, n, b, cap, a2a_caps, max_iter, min_gain, packed=packed,
        backend=backend, window_steps=window_steps, from_state=from_state)

    def run(*args):
        return engine(*args)[:3]

    return run


def _awpm_dist_batched(row, col, val, n: int, spec, *,
                       cap: int | None = None,
                       a2a_caps: tuple[int, int] | None = None,
                       max_iter: int = 1000, min_gain: float = MIN_GAIN,
                       packed: bool = False, backend: str = "fused"):
    """One-shot distributed-batched AWPM on the 2D(+pod) device grid
    (DESIGN.md §5): solves B padded [B, cap] COO instances in a single
    shard_map dispatch with per-instance convergence masks, edge state
    sharded [Pr, Pc, B, cap_blk] and O(n) state replicated. Per instance
    bit-identical to ``core.batch._awpm_batched`` (itself pinned to
    ``core.single._awpm``).

    ``spec`` is a GridSpec or a Mesh (axes ("data", "model")). Returns
    (MatchState with [B, n + 1] fields, awac_iters [B], dropped).

    Internal engine behind ``repro.core.api.solve`` (grid dispatch target)
    and the deprecated ``awpm_dist_batched`` shim."""
    if isinstance(spec, jax.sharding.Mesh):
        spec = GridSpec(spec)
    drv = _DistBatchedAWPM(spec, n, cap=cap, a2a_caps=a2a_caps,
                           max_iter=max_iter, min_gain=min_gain,
                           packed=packed, backend=backend)
    return drv.run(row, col, val)


def awpm_dist_batched(row, col, val, n: int, spec, *, cap: int | None = None,
                      a2a_caps: tuple[int, int] | None = None,
                      max_iter: int = 1000, min_gain: float = MIN_GAIN,
                      packed: bool = False, backend: str = "fused"):
    """Deprecated alias of the distributed-batched pipeline — use
    ``repro.core.api.solve`` with ``SolveOptions(grid=...)``."""
    warn_legacy("repro.core.dist.awpm_dist_batched", "solve()")
    return _awpm_dist_batched(
        row, col, val, n, spec, cap=cap, a2a_caps=a2a_caps,
        max_iter=max_iter, min_gain=min_gain, packed=packed, backend=backend)
