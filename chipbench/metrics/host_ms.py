"""Facade and host preparation (core/api.py, core/preflight.py), per
solve: each traced solve's wall time less the time device 0 was busy
inside it. Device trace, on the host spans' clock."""
from chipbench import trace


def read(ctx):
    return None if ctx.trace is None else trace.host_ms(ctx.trace, 0)
