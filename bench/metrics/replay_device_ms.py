"""Device time of the LWW replay per request answered in the profiled
part of the window, in ms (profiler trace): the ops that ran under the
program's ``replay`` name scope, in any program."""
from harness.scopes import scope_ms_per_answer


def read(ctx):
    return scope_ms_per_answer(ctx, "replay")
