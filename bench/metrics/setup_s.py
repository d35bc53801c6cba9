"""Process start to window start: data generation, load and its
flushes, compile or compile-cache load, warm-up (host clock)."""


def read(ctx):
    return ctx.setup_s
