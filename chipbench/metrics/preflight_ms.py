"""Preflight (core/api.py ``_apply_preflight``: the host scan of the edge
list), per solve: milliseconds inside its ``repro.preflight`` spans. Program span, read from
``repro.core.telemetry``'s records of the traced solves; None from a program
that keeps none."""

SPANS = ("repro.preflight",)


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    records = telemetry.recent(len(ctx.solves))
    if not ctx.solves or len(records) < len(ctx.solves):
        return None
    ns = sum(s["end_ns"] - s["start_ns"] for r in records for s in r["spans"]
             if s["name"] in SPANS)
    return ns / len(records) / 1e6
