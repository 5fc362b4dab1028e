"""Share of its roofline that one AWAC round reaches: the least time the
chip could take for the bytes the round must move (``sweep_bytes``), at the
peak HBM bandwidth of peaks.json, over the measured device time per round
(awac_ms / awac_rounds). The round is bound by memory: it does a few
operations per byte."""
from chipbench import trace


def sweep_bytes(m: int, n: int) -> int:
    """Least bytes one AWAC round moves, whatever implements it: each of the
    m edges' row, column and weight read once (12 B), one probe of the
    completing edge's key (4 B); the four n-long state arrays (mates and
    duals) read once and the four per-column winner arrays written once
    (32 B per vertex)."""
    return 16 * m + 32 * n


def read(ctx):
    if ctx.trace is None or 0 not in ctx.trace.devices:
        return None
    lo, hi = ctx.trace.window
    awac = trace.module_ns(ctx.trace.devices[0], ctx.modules["awac"], lo, hi)
    rounds = sum(s.awac_rounds for s in ctx.solves if s.awac_rounds)
    if awac is None or not rounds:
        return None
    least_s = sweep_bytes(ctx.nnz, ctx.n) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (awac / 1e9 / rounds)
