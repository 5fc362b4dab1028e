"""Windowed-search depth (core/single.py ``_resolve_window_steps``:
``sparse.csr.max_row_nnz`` on the host), per solve: milliseconds inside
its ``repro.window_depth`` spans. Program span, read from
``repro.core.telemetry``'s records of the traced solves; None from a program
that keeps none."""

SPANS = ("repro.window_depth",)


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
