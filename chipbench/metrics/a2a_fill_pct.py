"""Share of the grid exchange's bucket slots that carry a candidate: 100 x
the ``a2a_filled`` counter over the ``a2a_slots`` counter, summed over the
traced solves (core/dist.py ``a2a_bucketed_batched``: both all-to-all
stages, every AWAC round, every device). The rest is padding of the
worst-case bucket capacities (``safe_a2a_caps``). Program counter, read
from ``repro.core.telemetry``'s records; None from a program that keeps no
such counter or a solve that ran no grid exchange."""


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    records = telemetry.recent(len(ctx.solves))
    slots = [r["counters"].get("a2a_slots") for r in records]
    filled = [r["counters"].get("a2a_filled") for r in records]
    if not ctx.solves or len(records) < len(ctx.solves) \
            or None in slots or None in filled or not sum(slots):
        return None
    return 100.0 * sum(filled) / sum(slots)
