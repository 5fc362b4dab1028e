"""Triangular solves + mixed-precision iterative refinement (DESIGN.md §12).

The paper's pipeline commits to static pivots BEFORE factorization, so the
factorization is cheap-but-approximate and **iterative refinement** is
where accuracy is recovered — or visibly lost, which is the experiment:
AWPM-pivoted systems converge in a handful of sweeps, unpivoted
ill-conditioned systems diverge or stall. This module implements that
loop with the precision split real solvers use:

- the L/U factors are demoted to **float32/complex64** and the triangular
  sweeps run as jnp ``fori_loop`` kernels (the "fast, low-precision
  solve"),
- residuals ``r = b - A x`` are computed in **float64/complex128** host
  numpy against the ORIGINAL sparse matrix (the "accurate residual"),
  and corrections accumulate into a float64 iterate.

That split is what makes the refinement trajectory meaningful: a single
f32 solve lands around 1e-6; refinement against the f64 residual walks it
to ~1e-15 — unless pivot growth destroyed the factors, in which case the
trajectory visibly stalls or explodes. Per-RHS ``converged`` /
``diverged`` / ``stalled`` flags plus the full residual history are
returned, never just a final number.

Batching: the triangular sweeps are written once over ``[B, n]``
right-hand sides; a single RHS is solved as its own B=1 batch of the SAME
kernel (multiply+sum inner products, no shape-dependent blocking), so
batched and single solves agree bit-for-bit lane by lane — asserted by
``tests/test_solver.py``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.solver.lu import CsrMatrix, LUFactorization

__all__ = ["RefineResult", "lu_solve_once", "refine"]


# --------------------------------------------------------------------------
# jnp triangular sweeps (the low-precision inner solver)
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=())
def _solve_unit_lower(l_strict, b):
    """x of (I + L_strict) x = b, forward sweep, b: [B, n].

    Row k's inner product is a masked multiply+sum over the full width —
    identical arithmetic for every batch size (no triangular blocking), so
    B=1 and B=8 lanes agree bit-for-bit.
    """
    n = b.shape[-1]

    def body(k, x):
        s = jnp.sum(l_strict[k] * x, axis=-1)
        return x.at[:, k].set(b[:, k] - s)

    return jax.lax.fori_loop(0, n, body, jnp.zeros_like(b))


@functools.partial(jax.jit, static_argnames=())
def _solve_upper(u_strict, u_diag, b):
    """x of (diag(u_diag) + U_strict) x = b, backward sweep, b: [B, n]."""
    n = b.shape[-1]

    def body(i, x):
        k = n - 1 - i
        s = jnp.sum(u_strict[k] * x, axis=-1)
        return x.at[:, k].set((b[:, k] - s) / u_diag[k])

    return jax.lax.fori_loop(0, n, body, jnp.zeros_like(b))


def _dense_factors(factor: LUFactorization):
    """Demote the CSR factors to dense f32/c64 sweep operands once."""
    complex_in = np.iscomplexobj(factor.U.data)
    dt = np.complex64 if complex_in else np.float32
    with np.errstate(over="ignore"):  # growth-blown factors overflow f32
        l_strict = factor.L.to_dense().astype(dt)  # on purpose: the inf
        u_strict = factor.U.to_dense().astype(dt)  # surfaces as divergence
    u_diag = np.diag(u_strict).copy()
    np.fill_diagonal(u_strict, 0)
    return jnp.asarray(l_strict), jnp.asarray(u_strict), jnp.asarray(u_diag)


def lu_solve_once(factor: LUFactorization, b: np.ndarray) -> np.ndarray:
    """One low-precision solve ``x ~ A^-1 b`` through the factors
    (applies the factorization's internal row permutation). ``b`` is
    ``[n]`` or ``[B, n]``; the single-RHS form is the B=1 lift."""
    l_strict, u_strict, u_diag = _dense_factors(factor)
    b = np.asarray(b)
    single = b.ndim == 1
    bb = b[None, :] if single else b
    x = _apply_factors(l_strict, u_strict, u_diag, factor.row_perm, bb)
    x = np.asarray(x, dtype=np.complex128 if np.iscomplexobj(u_diag)
                   else np.float64)
    return x[0] if single else x


def _apply_factors(l_strict, u_strict, u_diag, row_perm, b):
    dt = l_strict.dtype
    pb = jnp.asarray(np.asarray(b)[..., row_perm], dtype=dt)
    y = _solve_unit_lower(l_strict, pb)
    return _solve_upper(u_strict, u_diag, y)


# --------------------------------------------------------------------------
# the refinement loop (high-precision residuals, host side)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RefineResult:
    """Outcome of refining a batch of B right-hand sides.

    ``residuals[t, b]`` is lane b's relative residual
    ``||r||_2 / ||rhs||_2`` before iteration t (so ``residuals[0]`` is the
    quality of the raw f32 solve's starting point — all-ones, since x
    starts at 0). Frozen lanes (converged / diverged / stalled) repeat
    their final residual in later rows, keeping the array rectangular.
    """

    x: np.ndarray  # [B, n] float64 / complex128
    residuals: np.ndarray  # [T, B] float64 relative residuals
    iterations: np.ndarray  # [B] int64 — sweeps actually applied per lane
    converged: np.ndarray  # [B] bool
    diverged: np.ndarray  # [B] bool
    stalled: np.ndarray  # [B] bool
    tol: float

    @property
    def all_converged(self) -> bool:
        return bool(self.converged.all())

    @property
    def final_residual(self) -> np.ndarray:
        """[B] — each lane's last recorded relative residual."""
        return self.residuals[-1]


def _csr_matvec(a: CsrMatrix, x: np.ndarray) -> np.ndarray:
    """f64/c128 host matvec ``A @ x`` for x: [B, n] (exact residual path —
    deliberately NOT the f32 device path being refined)."""
    out = np.zeros_like(x)
    for i in range(a.n):
        lo, hi = int(a.indptr[i]), int(a.indptr[i + 1])
        # multiply + pairwise sum over the LAST axis only: accumulation
        # order per lane is independent of B (a BLAS `@` here picks
        # shape-dependent kernels and breaks batched/single bit-equality)
        out[:, i] = np.sum(x[:, a.indices[lo:hi]] * a.data[lo:hi], axis=-1)
    return out


def refine(a: CsrMatrix, factor: LUFactorization, b: np.ndarray, *,
           tol: float = 1e-12, max_iter: int = 40,
           stall_window: int = 3, stall_factor: float = 0.5,
           divergence_factor: float = 1e4,
           row_weight: np.ndarray | None = None) -> RefineResult:
    """Iteratively refine ``A x = b`` through the (possibly perturbed,
    possibly garbage) factors of ``a``.

    ``b`` is ``[n]`` or ``[B, n]``; a single RHS runs as the B=1 lift of
    the batched path and is squeezed on return. Per lane, iteration stops
    on the first of: **converged** (relative residual <= tol),
    **diverged** (residual non-finite, or > divergence_factor x the best
    seen), **stalled** (no ``stall_factor`` improvement across
    ``stall_window`` consecutive sweeps), or ``max_iter``. Frozen lanes
    stop updating — their x is exactly what it was at freeze time — while
    live lanes continue, so one bad RHS never poisons its batch.

    ``row_weight`` ([n], positive) measures every residual as
    ``||w * r||_2 / ||w * b||_2``. A caller refining a row-scaled system
    ``(D A) x = D b`` passes ``w = 1 / D`` so that convergence is judged
    in the frame of the ORIGINAL system: a residual that is tiny after
    scaling can still be large before it.
    """
    b = np.asarray(b)
    single = b.ndim == 1
    complex_sys = np.iscomplexobj(a.data) or np.iscomplexobj(b)
    acc = np.complex128 if complex_sys else np.float64
    bb = (b[None, :] if single else b).astype(acc)
    B, n = bb.shape
    if n != a.n:
        raise ValueError(f"rhs width {n} != matrix order {a.n}")

    l_strict, u_strict, u_diag = _dense_factors(factor)
    w = np.ones(n) if row_weight is None else np.asarray(row_weight, np.float64)
    bnorm = np.linalg.norm(w * bb, axis=-1)
    bnorm = np.where(bnorm == 0.0, 1.0, bnorm)

    x = np.zeros((B, n), acc)
    live = np.ones(B, bool)
    converged = np.zeros(B, bool)
    diverged = np.zeros(B, bool)
    iterations = np.zeros(B, np.int64)
    best = np.full(B, np.inf)
    since_improve = np.zeros(B, np.int64)
    history = []

    for _ in range(max_iter + 1):
        r = bb - _csr_matvec(a, x)
        rel = np.linalg.norm(w * r, axis=-1) / bnorm
        # frozen lanes keep their freeze-time residual on the record
        if history:
            rel = np.where(live, rel, history[-1])
        history.append(rel)

        hit = live & (rel <= tol)
        converged |= hit
        live &= ~hit
        blown = live & (~np.isfinite(rel) | (rel > divergence_factor *
                                             np.minimum(best, 1.0)))
        diverged |= blown
        live &= ~blown
        improved = rel < stall_factor * best
        since_improve = np.where(improved, 0, since_improve + 1)
        best = np.minimum(best, np.where(np.isfinite(rel), rel, np.inf))
        stalled_now = live & (since_improve >= stall_window)
        live &= ~stalled_now
        if not live.any():
            break

        # one low-precision correction sweep; frozen lanes masked out so
        # their x (and thus their recorded residual) never moves again
        d = np.asarray(
            _apply_factors(l_strict, u_strict, u_diag, factor.row_perm, r),
            dtype=acc)
        d = np.where(np.isfinite(d), d, 0.0)
        x = x + np.where(live[:, None], d, 0.0)
        iterations += live.astype(np.int64)

    stalled = ~(converged | diverged) & (np.asarray(history[-1]) > tol)
    result = RefineResult(
        x=x[0] if single else x,
        residuals=np.asarray(history),
        iterations=iterations,
        converged=converged,
        diverged=diverged,
        stalled=stalled,
        tol=float(tol))
    return result
