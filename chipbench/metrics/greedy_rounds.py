"""Greedy proposal rounds per solve (core/single.py ``_greedy_rounds``), the
last of which matches nothing: the ``greedy_rounds`` counter, counted on
the device. Program counter, read from
``repro.core.telemetry``'s records of the traced solves; None from a program
that keeps none."""


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    records = telemetry.recent(len(ctx.solves))
    counts = [r["counters"].get("greedy_rounds") for r in records]
    if not ctx.solves or len(counts) < len(ctx.solves) or None in counts:
        return None
    return sum(counts) / len(counts)
