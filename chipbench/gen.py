"""Instance generation for the benchmark: the seeded matrix families and the
drift model, kept here so that no change to the program can move the
yardstick.

``generate``, ``from_coo`` and ``normalize_rowcol_max`` are copies of
``repro.core.graph`` that give the same bits (``tests/test_bench_gen.py``
holds them to that). ``drift_chain`` is the traffic's chain of drifting
values on one fixed pattern: each link is the one before it under
``repro.serving.loadgen.perturbed`` (no structure churn) and renormalized
per the paper's §6.1, bit for bit, computed without re-sorting the
unchanged pattern.

An instance is a ``Graph``: the padded, lex-sorted COO convention of the
program (padding entries ``(n, n, 0)``).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Graph:
    n: int
    nnz: int
    row: np.ndarray  # [cap] int32
    col: np.ndarray  # [cap] int32
    val: np.ndarray  # [cap] float32


def _dedupe(row, col, val):
    key = row.astype(np.int64) * (col.max() + 1 if col.size else 1) + col
    _, idx = np.unique(key, return_index=True)
    return row[idx], col[idx], val[idx]


def from_coo(row, col, val, n, capacity=None, pad_align: int = 8) -> Graph:
    row = np.asarray(row, dtype=np.int32)
    col = np.asarray(col, dtype=np.int32)
    val = np.asarray(val, dtype=np.float32)
    order = np.lexsort((col, row))
    row, col, val = row[order], col[order], val[order]
    nnz = int(row.shape[0])
    if capacity is None:
        capacity = max(((nnz + pad_align - 1) // pad_align) * pad_align, pad_align)
    if capacity < nnz:
        raise ValueError(f"capacity {capacity} < nnz {nnz}")
    pad = capacity - nnz
    row = np.concatenate([row, np.full(pad, n, np.int32)])
    col = np.concatenate([col, np.full(pad, n, np.int32)])
    val = np.concatenate([val, np.zeros(pad, np.float32)])
    return Graph(n=n, nnz=nnz, row=row, col=col, val=val)


def normalize_rowcol_max(row, col, val):
    """Paper §6.1: the largest entry of each row and column is 1."""
    val = np.abs(val).astype(np.float64)
    n = int(max(row.max(), col.max())) + 1 if row.size else 0
    rmax = np.zeros(n)
    np.maximum.at(rmax, row, val)
    val = val / np.maximum(rmax[row], 1e-300)
    cmax = np.zeros(n)
    np.maximum.at(cmax, col, val)
    val = val / np.maximum(cmax[col], 1e-300)
    return val.astype(np.float32)


def generate(n: int, avg_degree: float = 4.0, kind: str = "uniform",
             seed: int = 0, normalize: bool = True,
             capacity: int | None = None) -> Graph:
    """Square matrix with a planted perfect matching (a hidden random
    permutation), in one of the structure families of the paper's §6.
    ``capacity`` pads every seed to the same edge count, so that one
    compiled program serves them all."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n).astype(np.int32)
    rows = [np.arange(n, dtype=np.int32)]
    cols = [perm]
    m_extra = int(n * max(avg_degree - 1.0, 0.0))

    if kind == "banded":
        band = max(int(3 * avg_degree), 2)
        r = rng.integers(0, n, size=m_extra).astype(np.int32)
        off = rng.integers(-band, band + 1, size=m_extra)
        c = np.clip(r + off, 0, n - 1).astype(np.int32)
    elif kind in ("powerlaw", "circuit", "antigreedy"):
        popularity = 1.0 / (1.0 + np.arange(n)) ** 0.8
        popularity /= popularity.sum()
        r = rng.integers(0, n, size=m_extra).astype(np.int32)
        c = rng.choice(n, size=m_extra, p=popularity).astype(np.int32)
    elif kind == "uniform":
        r = rng.integers(0, n, size=m_extra).astype(np.int32)
        c = rng.integers(0, n, size=m_extra).astype(np.int32)
    else:
        raise ValueError(f"unknown matrix family {kind!r}")
    rows.append(r)
    cols.append(c)
    row = np.concatenate(rows)
    col = np.concatenate(cols)

    if kind == "circuit":
        val = rng.uniform(0.0, 0.5, size=row.shape[0])
        val[:n] = rng.uniform(0.8, 1.0, size=n)
    elif kind == "antigreedy":
        val = rng.uniform(0.9, 1.0, size=row.shape[0])
        val[:n] = rng.uniform(0.5, 0.6, size=n)
    else:
        val = rng.uniform(1e-3, 1.0, size=row.shape[0])

    row, col, val = _dedupe(row, col, val.astype(np.float32))
    if normalize:
        val = normalize_rowcol_max(row, col, val)
    return from_coo(row, col, val, n, capacity=capacity)


def drift_chain(base: Graph, length: int, rng: np.random.Generator,
                weight_jitter: float) -> list[np.ndarray]:
    """``length`` value arrays ([cap] float32, padding 0) on ``base``'s
    pattern. Link 0 is ``base.val``; link k is link k-1 under
    ``loadgen.perturbed(weight_jitter, structure_churn=0)``, renormalized by
    ``normalize_rowcol_max``, bit for bit. The pattern is sorted and fixed,
    so the row and column maxima come from segment reductions over a sort
    order computed once."""
    nnz, n = base.nnz, base.n
    row, col = base.row[:nnz], base.col[:nnz]
    n_norm = int(max(row.max(), col.max())) + 1
    row_starts = np.flatnonzero(np.r_[True, row[1:] != row[:-1]])
    by_col = np.argsort(col, kind="stable")
    col_sorted = col[by_col]
    col_starts = np.flatnonzero(np.r_[True, col_sorted[1:] != col_sorted[:-1]])
    rows_present = row[row_starts]
    cols_present = col_sorted[col_starts]
    out = [base.val.copy()]
    for _ in range(length - 1):
        val = out[-1][:nnz].astype(np.float64)
        val = np.abs(val * (1.0 + weight_jitter * rng.standard_normal(nnz)))
        val = np.maximum(val, 1e-6)
        val = np.abs(val.astype(np.float32)).astype(np.float64)
        rmax = np.zeros(n_norm)
        rmax[rows_present] = np.maximum.reduceat(val, row_starts)
        val = val / np.maximum(rmax[row], 1e-300)
        cmax = np.zeros(n_norm)
        cmax[cols_present] = np.maximum.reduceat(val[by_col], col_starts)
        val = val / np.maximum(cmax[col], 1e-300)
        link = np.zeros_like(base.val)
        link[:nnz] = val.astype(np.float32)
        out.append(link)
    return out
