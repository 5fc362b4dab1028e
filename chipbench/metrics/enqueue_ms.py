"""Engine calls (greedy, MCM, ``row_ptr_from_sorted`` and the AWAC loop:
host arrays converted and copied, programs enqueued), per solve:
milliseconds inside their ``repro.greedy``, ``repro.mcm``,
``repro.row_ptr`` and ``repro.awac`` spans. Program span, read from
``repro.core.telemetry``'s records of the traced solves; None from a program
that keeps none."""

SPANS = ("repro.greedy", "repro.mcm", "repro.row_ptr", "repro.awac")


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
