"""jit'd public wrappers for the cycle_gain kernel package: padding +
dispatch of the dense tile kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret
from repro.kernels.cycle_gain.cycle_gain import cycle_gain
from repro.kernels.cycle_gain.ref import cycle_gain_ref


def _round_up(x, m):
    return (x + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("tm", "tn", "use_kernel", "interpret"))
def cycle_gain_padded(a, a2, u, v, *, tm: int = 256, tn: int = 256,
                      use_kernel: bool = True, interpret: bool | None = None):
    """Pads (M, N) up to tile multiples and dispatches to the Pallas kernel
    (or the jnp reference when ``use_kernel=False`` — used by XLA-only
    paths). ``interpret=None`` auto-detects: compiled on TPU, interpreter
    elsewhere."""
    m, n = a.shape
    if not use_kernel:
        return cycle_gain_ref(a, a2, u, v)
    tm = min(tm, _round_up(m, 8))
    tn = min(tn, _round_up(n, 128))
    mp, np_ = _round_up(m, tm), _round_up(n, tn)
    a_p = jnp.zeros((mp, np_), a.dtype).at[:m, :n].set(a)
    a2_p = jnp.zeros((mp, np_), a2.dtype).at[:m, :n].set(a2)
    u_p = jnp.zeros((mp,), u.dtype).at[:m].set(u)
    v_p = jnp.zeros((np_,), v.dtype).at[:n].set(v)
    g, r = cycle_gain(a_p, a2_p, u_p, v_p, tm=tm, tn=tn,
                      interpret=resolve_interpret(interpret))
    return g[:n], r[:n]


def swap_gains(affinity, assign_expert, tok_affinity, *, use_kernel=True,
               interpret=None):
    """AWPM-router building block: gains of swapping token i's expert with the
    expert owning slot j.

    affinity [T, E]: token->expert affinity (dense).
    assign_expert [T] int: current expert of each token.
    tok_affinity [T]: affinity of each token's current assignment.

    Returns gain [T, T] is too big; instead this evaluates the bipartite
    token x token swap through the cycle_gain contract: A[i, j] =
    affinity[i, expert[j]] (i moving to j's expert), A2[i, j] =
    affinity[j, expert[i]], u[i] = v[i] = tok_affinity[i]. The per-column
    winner is each token j's best swap partner. Computed tile-wise by the
    kernel without materializing [T, T] in HBM when T is tiled by the caller.
    """
    a = jnp.take(affinity, assign_expert, axis=1)  # [T, T]: aff[i, e_j]
    a2 = a.T
    return cycle_gain_padded(a, a2, tok_affinity, tok_affinity,
                             use_kernel=use_kernel, interpret=interpret)
