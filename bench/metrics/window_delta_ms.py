"""Window-delta materialization per engine call, in ms: the summed
duration of the program's ``window_delta`` spans over the number of
``query`` spans in the window."""
from harness.spans import per_call_ms


def read(ctx):
    return per_call_ms(ctx.spans, "window_delta")
