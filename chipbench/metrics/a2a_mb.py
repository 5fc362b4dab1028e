"""Megabytes the grid exchange's all-to-alls carry per solve: the
``a2a_bytes`` counter, ``a2a_slots`` times the bytes a slot carries
(core/dist.py ``a2a_slot_bytes``), over both stages, every AWAC round and
every device. Program counter, read from ``repro.core.telemetry``'s
records of the traced solves; None from a program that keeps no such
counter or a solve that ran no grid exchange."""


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    records = telemetry.recent(len(ctx.solves))
    counts = [r["counters"].get("a2a_bytes") for r in records]
    if not ctx.solves or len(counts) < len(ctx.solves) or None in counts:
        return None
    return 1e-6 * sum(counts) / len(counts)
