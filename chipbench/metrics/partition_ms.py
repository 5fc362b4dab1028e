"""Grid partition (sparse/partition.py ``partition_coo_2d_batched``: the
host sorts the edge list into the grid's 2D blocks on every call), per
solve: milliseconds inside its ``repro.partition`` spans. Program span,
read from ``repro.core.telemetry``'s records of the traced solves; None
from a program that keeps no records or a route that partitions nothing."""

SPANS = ("repro.partition",)


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    records = telemetry.recent(len(ctx.solves))
    if not ctx.solves or len(records) < len(ctx.solves):
        return None
    spans = [s for r in records for s in r["spans"] if s["name"] in SPANS]
    if not spans:
        return None
    ns = sum(s["end_ns"] - s["start_ns"] for s in spans)
    return ns / len(records) / 1e6
