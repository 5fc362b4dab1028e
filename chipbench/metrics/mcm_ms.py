"""Maximum cardinality matching (single.mcm), per solve: device time on device 0 of
the XLA modules that modules.json names for the phase. Device trace."""
from chipbench import trace


def read(ctx):
    return trace.phase_ms(ctx.trace, ctx.modules["mcm"])
