"""AWAC rounds per solve, as MatchResult.awac_iters counts them (exact),
averaged over the traced solves."""


def read(ctx):
    rounds = [s.awac_rounds for s in ctx.solves if s.awac_rounds is not None]
    return sum(rounds) / len(rounds) if rounds else None
