"""Share of the columns that greedy matches, per solve: the
``greedy_matched`` counter (counted on the device) over n. The rest is
MCM's to match. Program counter, read from
``repro.core.telemetry``'s records of the traced solves; None from a program
that keeps none."""


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    records = telemetry.recent(len(ctx.solves))
    counts = [r["counters"].get("greedy_matched") for r in records]
    if not ctx.solves or len(counts) < len(ctx.solves) or None in counts:
        return None
    return 100.0 / ctx.n * sum(counts) / len(counts)
