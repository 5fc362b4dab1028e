"""Instances per solve whose edges the grid's host partition found
lex-sorted and split into its 2D blocks by one linear pass
(sparse/partition.py ``_block_order``), not by the three-key lexsort: the
``partition_sorted`` counter. Program counter, read from
``repro.core.telemetry``'s records of the traced solves; None from a
program that keeps no such counter or a route that partitions nothing."""


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    records = telemetry.recent(len(ctx.solves))
    counts = [r["counters"].get("partition_sorted") for r in records]
    if not ctx.solves or len(counts) < len(ctx.solves) or None in counts:
        return None
    return sum(counts) / len(counts)
