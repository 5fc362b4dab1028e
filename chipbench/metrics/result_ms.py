"""Result (core/api.py ``_result``: the weight summed in its fixed pairwise
order, and the perfect flag), per solve: milliseconds in which device 0 ran
no operation while the ``repro.result`` span was open, the host work of
``_result`` that the device waits for. Device trace: the span's times come
from ``repro.core.telemetry``'s records of the traced solves (host clock)
and are placed on the trace's clock by each solve's root span
``repro.solve``, which runs inside the benchmark's ``solve`` span. None
from a program that keeps no records."""
from chipbench import trace

SPAN = "repro.result"
ROOT = "repro.solve"


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    tr = ctx.trace
    if tr is None or 0 not in tr.devices or not ctx.solves:
        return None
    outer = [(s, e) for s, e, name in tr.spans if name == trace.SPANS[1]]
    records = telemetry.recent(len(ctx.solves))
    if len(records) != len(ctx.solves) or len(outer) != len(records):
        return None
    merged = trace.union(tr.devices[0].ops)
    idle = 0.0
    for (lo, hi), rec in zip(outer, records):
        root = [s for s in rec["spans"] if s["name"] == ROOT]
        if len(root) != 1:
            return None
        r0, r1 = root[0]["start_ns"], root[0]["end_ns"]
        scale = (hi - lo) / (r1 - r0)
        for s in rec["spans"]:
            if s["name"] == SPAN:
                a = lo + (s["start_ns"] - r0) * scale
                b = lo + (s["end_ns"] - r0) * scale
                idle += (b - a) - trace.covered(merged, a, b)
    return idle / len(records) / 1e6
