"""Share of the profiled window in which no operation ran on the
device, in % (profiler trace: 1 - union of op intervals / window)."""


def read(ctx):
    return None if ctx.profile is None else ctx.profile["idle_share"] * 100
