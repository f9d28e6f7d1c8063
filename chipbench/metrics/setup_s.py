"""Set-up seconds: process start to the first timed request (import, chip
start, data from the seed, compile-cache load, one warm-up request)."""


def read(ctx):
    return ctx.setup_s
