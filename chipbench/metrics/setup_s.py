"""Set-up seconds: process start to the end of the warm-up solve (imports,
device start, instance and drift chain, compile or cache fetch, one
solve). Host clock."""


def read(ctx):
    return ctx.setup_s
