"""Mean submit-to-dispatch wait in the micro-batching frontend over the
window, in ms: the sum and count the registry histogram
``frontend_queue_wait_seconds`` gained (program counter)."""


def read(ctx):
    s, n = ctx.histogram_delta("frontend_queue_wait_seconds")
    return s / n * 1e3 if n else None
