"""Seconds per matching on one chip: the window's seconds over the
matchings it completed (host arrays in to mates in host memory, closed
loop). Host clock."""


def read(ctx):
    if ctx.window_s is None or not ctx.solves:
        return None
    return ctx.window_s / len(ctx.solves)
