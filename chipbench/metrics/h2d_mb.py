"""Host-to-device copies of the engines' jitted calls (every host array
handed to one is copied anew), per solve: the ``h2d_bytes`` counter in
megabytes. Program counter, read from
``repro.core.telemetry``'s records of the traced solves; None from a program
that keeps none."""


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    records = telemetry.recent(len(ctx.solves))
    counts = [r["counters"].get("h2d_bytes") for r in records]
    if not ctx.solves or len(counts) < len(ctx.solves) or None in counts:
        return None
    return 1e-6 * sum(counts) / len(counts)
