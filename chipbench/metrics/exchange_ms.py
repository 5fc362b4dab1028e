"""Grid exchange (core/dist.py: the all-to-alls of AWAC Steps A/B, the
all-gathers of every phase, the psums), per solve: the time device 0 ran a
collective operation inside the traced window, the union of their
intervals. Device trace; None where device 0 ran none (one chip).

On the TPU the trace names some collectives after the JAX primitive
(``all_to_all.3``, ``psum.1``) and others after the HLO operation
(``all-gather.12``); ``trace.collective_ns`` matches the HLO spellings
alone, so this reader matches both."""
import re

from chipbench import trace

COLLECTIVE = re.compile(
    r"(all-gather|all_gather|all-to-all|all_to_all|all-reduce|all_reduce"
    r"|psum|reduce-scatter|reduce_scatter|collective-permute|ppermute"
    r"|collective-broadcast)")


def read(ctx):
    tr = ctx.trace
    if tr is None or 0 not in tr.devices:
        return None
    coll = [op for op in tr.devices[0].ops if COLLECTIVE.match(op[2])]
    if not coll:
        return None
    lo, hi = tr.window
    return trace.per_solve_ms(trace.covered(trace.union(coll), lo, hi), tr)
