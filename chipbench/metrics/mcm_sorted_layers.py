"""BFS layers per solve whose parent choice ran the sorted-segment scan
(core/single.py ``_mcm_bfs``): the ``mcm_sorted_layers`` counter, equal to
``mcm_bfs_layers`` where the mechanism engaged on every layer. Program
counter, read from ``repro.core.telemetry``'s records of the traced solves;
None from a program that keeps no such counter."""


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    records = telemetry.recent(len(ctx.solves))
    counts = [r["counters"].get("mcm_sorted_layers") for r in records]
    if not ctx.solves or len(counts) < len(ctx.solves) or None in counts:
        return None
    return sum(counts) / len(counts)
