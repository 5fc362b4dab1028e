"""Device time of one BFS layer: ``mcm_ms`` (the MCM modules' device time
per solve, trace) over the ``mcm_bfs_layers`` counter per solve, read from
``repro.core.telemetry``'s records of the traced solves. Includes each
phase's trace and flip. None from a program that keeps no records."""
from chipbench import trace


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    mcm = trace.phase_ms(ctx.trace, ctx.modules["mcm"])
    records = telemetry.recent(len(ctx.solves))
    layers = [r["counters"].get("mcm_bfs_layers") for r in records]
    if mcm is None or not ctx.solves or len(layers) < len(ctx.solves) \
            or None in layers or not sum(layers):
        return None
    return mcm / (sum(layers) / len(layers))
