"""Planner self time per engine call, in ms: the summed self time of
the program's ``plan`` spans over the number of ``query`` spans (one
per ``evaluate_many`` call) in the window."""
from harness.spans import per_call_ms


def read(ctx):
    return per_call_ms(ctx.spans, "plan", self_time=True)
