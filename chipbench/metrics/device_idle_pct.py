"""Share of the traced window in which device 0 ran no operation:
1 - (union of its op intervals) / window. Device trace."""
from chipbench import trace


def read(ctx):
    return None if ctx.trace is None else trace.idle_pct(ctx.trace, 0)
