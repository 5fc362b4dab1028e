"""2D block partitioning of a sparse matrix over a Pr x Pc process grid.

This mirrors the paper's CombBLAS-style regular 2D distribution: process (a, b)
owns the dense index block rows [a*br, (a+1)*br) x cols [b*bc, (b+1)*bc).
Per-block edge lists are padded to a common capacity so the stacked arrays
[Pr, Pc, cap] shard cleanly under shard_map with PartitionSpec("data","model").

Entries store GLOBAL indices (int32). Padding entries have row = col = n (the
global sentinel) and val = 0; every consumer masks on ``row < n``.

Each block holds its edges sorted by (row, col), in input order among equal
pairs: the order ``np.lexsort((col, row, block))`` gives. Edges that arrive
lex-sorted by (row, col), as the grid driver asks and
``MatchingProblem.from_coo`` makes them, already hold that order inside each
block, so one stable sort by block id alone gives it in O(nnz)
(``_block_order``); any other input falls back to the three-key lexsort,
with the same result.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Partition2D:
    n: int  # global rows == cols (square, per the paper)
    pr: int
    pc: int
    br: int  # block rows  = ceil(n / pr)
    bc: int  # block cols  = ceil(n / pc)
    cap: int  # per-block edge capacity
    nnz: np.ndarray  # [pr, pc] int32 actual nnz per block
    row: np.ndarray  # [pr, pc, cap] int32 global row ids, lex-sorted per block
    col: np.ndarray  # [pr, pc, cap] int32 global col ids
    val: np.ndarray  # [pr, pc, cap] float32

    def block_of(self, i, j):
        return i // self.br, j // self.bc


@dataclasses.dataclass
class Partition2DBatched:
    """A batch of B instances partitioned over the SAME Pr x Pc grid with a
    shared per-block capacity, stacked [pr, pc, B, cap] so the arrays shard
    under shard_map with PartitionSpec("data", "model", None, None) — each
    device holds its block of every instance and the batched collectives
    amortize across B."""

    n: int
    b: int
    pr: int
    pc: int
    br: int
    bc: int
    cap: int  # shared per-block edge capacity (true max occupancy, padded)
    nnz: np.ndarray  # [pr, pc, B] int32 actual nnz per (block, instance)
    row: np.ndarray  # [pr, pc, B, cap] int32 global rows, lex-sorted per block
    col: np.ndarray  # [pr, pc, B, cap] int32 global cols
    val: np.ndarray  # [pr, pc, B, cap] float32
    sorted_instances: int  # instances whose edges arrived lex-sorted


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _block_ids(row, col, n: int, pr: int, pc: int):
    """One padded instance's real edges (``row < n``) and each one's block,
    ``(row // br) * pc + col // bc``: (rows, cols, mask, block ids)."""
    br = -(-n // pr)
    bc = -(-n // pc)
    m = row < n
    r, c = row[m], col[m]
    return r, c, m, (r // br) * pc + c // bc


def block_occupancy(row, col, n: int, pr: int, pc: int) -> np.ndarray:
    """True per-block nnz counts of a padded COO instance ([cap] arrays,
    padding row == n) or batch ([B, cap]). Returns [pr, pc] (or [B, pr, pc]).
    This is the measurement capacity planning must be based on — the uniform
    m / (pr * pc) estimate undercounts adversarially skewed instances (one
    dense row lands entirely in a single grid row)."""
    row = np.asarray(row)
    col = np.asarray(col)
    if row.ndim == 2:
        return np.stack([
            block_occupancy(r, c, n, pr, pc) for r, c in zip(row, col)
        ])
    blk = _block_ids(row, col, n, pr, pc)[3]
    return np.bincount(blk, minlength=pr * pc).reshape(pr, pc).astype(np.int32)


def _block_order(r, c, blk, n: int) -> tuple[np.ndarray, bool]:
    """The permutation that orders the edges by (block, row, col), stable
    among equal pairs: ``np.lexsort((c, r, blk))``'s. Also whether it took
    the linear pass: where the keys ``r * (n + 1) + c`` are non-decreasing
    (checked in O(nnz)), a stable sort by block id alone keeps each block's
    edges in that order, and numpy sorts a key of 16 bits or fewer by radix.
    Any other input falls back to the lexsort."""
    key = r.astype(np.int64) * (n + 1) + c
    if bool((key[1:] >= key[:-1]).all()):
        small = blk.astype(np.min_scalar_type(int(blk.max(initial=0))))
        return np.argsort(small, kind="stable"), True
    return np.lexsort((c, r, blk)), False


def _fill_blocks(R, C, V, r, c, v, order, counts) -> None:
    """Block p's edges, ``order``'s run p of ``counts[p]``, into R[p], C[p],
    V[p] (the rest keeps its padding)."""
    starts = np.zeros(len(counts) + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    for p in range(R.shape[0]):
        idx = order[starts[p]:starts[p + 1]]
        R[p, :idx.size] = r[idx]
        C[p, :idx.size] = c[idx]
        V[p, :idx.size] = v[idx]


def plan_block_cap(row, col, n: int, pr: int, pc: int,
                   pad_align: int = 8) -> int:
    """Per-block edge capacity derived from the TRUE max block occupancy
    (never the uniform nnz / (pr * pc) spread). Accepts [cap] or [B, cap]
    padded COO index arrays."""
    occ = int(block_occupancy(row, col, n, pr, pc).max(initial=0))
    return max(_round_up(occ, pad_align), pad_align)


def partition_coo_2d(
    row, col, val, n: int, pr: int, pc: int, cap: int | None = None, pad_align: int = 8
) -> Partition2D:
    row = np.asarray(row, dtype=np.int32)
    col = np.asarray(col, dtype=np.int32)
    val = np.asarray(val, dtype=np.float32)
    br = -(-n // pr)
    bc = -(-n // pc)
    blk = (row // br) * pc + col // bc
    order = _block_order(row, col, blk, n)[0]
    counts = np.bincount(blk, minlength=pr * pc)
    max_nnz = int(counts.max()) if counts.size else 0
    if cap is None:
        cap = max(_round_up(max_nnz, pad_align), pad_align)
    if cap < max_nnz:
        raise ValueError(
            f"cap {cap} < max block nnz {max_nnz}: refusing to truncate "
            f"edges (capacity must come from true block occupancy, see "
            f"plan_block_cap)")
    R = np.full((pr * pc, cap), n, dtype=np.int32)
    C = np.full((pr * pc, cap), n, dtype=np.int32)
    V = np.zeros((pr * pc, cap), dtype=np.float32)
    _fill_blocks(R, C, V, row, col, val, order, counts)
    return Partition2D(
        n=n,
        pr=pr,
        pc=pc,
        br=br,
        bc=bc,
        cap=cap,
        nnz=counts.reshape(pr, pc).astype(np.int32),
        row=R.reshape(pr, pc, cap),
        col=C.reshape(pr, pc, cap),
        val=V.reshape(pr, pc, cap),
    )


def partition_coo_2d_batched(
    row, col, val, n: int, pr: int, pc: int, cap: int | None = None,
    pad_align: int = 8,
) -> Partition2DBatched:
    """Partition a batch of padded [B, cap_in] COO instances (shared n,
    padding entries (n, n, 0)) over one Pr x Pc grid with a SHARED per-block
    capacity.

    ``cap=None`` derives the capacity from the true max block occupancy
    across every (instance, block) pair (``plan_block_cap``). An explicit
    ``cap`` smaller than that occupancy raises — edges are never silently
    overflow-truncated, because a dropped edge would silently degrade the
    matching weight on exactly the adversarial (skewed) instances.
    """
    row = np.asarray(row, dtype=np.int32)
    col = np.asarray(col, dtype=np.int32)
    val = np.asarray(val, dtype=np.float32)
    if row.ndim != 2:
        raise ValueError(f"expected batched [B, cap] arrays, got {row.shape}")
    b = row.shape[0]
    br = -(-n // pr)
    bc = -(-n // pc)
    edges, sorted_instances = [], 0
    for i in range(b):
        r, c, m, blk = _block_ids(row[i], col[i], n, pr, pc)
        order, linear = _block_order(r, c, blk, n)
        sorted_instances += linear
        edges.append((r, c, val[i][m], order,
                      np.bincount(blk, minlength=pr * pc)))
    occ = np.stack([counts for *_, counts in edges]).reshape(b, pr, pc)
    max_occ = int(occ.max(initial=0))
    if cap is None:
        cap = max(_round_up(max_occ, pad_align), pad_align)
    if cap < max_occ:
        raise ValueError(
            f"cap {cap} < max block occupancy {max_occ}: refusing to "
            f"truncate edges (derive capacity with plan_block_cap)")
    R = np.full((pr * pc, b, cap), n, dtype=np.int32)
    C = np.full((pr * pc, b, cap), n, dtype=np.int32)
    V = np.zeros((pr * pc, b, cap), dtype=np.float32)
    for i, (r, c, v, order, counts) in enumerate(edges):
        _fill_blocks(R[:, i], C[:, i], V[:, i], r, c, v, order, counts)
    return Partition2DBatched(
        n=n, b=b, pr=pr, pc=pc, br=br, bc=bc, cap=cap,
        nnz=np.transpose(occ, (1, 2, 0)).astype(np.int32),
        row=R.reshape(pr, pc, b, cap),
        col=C.reshape(pr, pc, b, cap),
        val=V.reshape(pr, pc, b, cap),
        sorted_instances=sorted_instances,
    )
