"""Share of the replayed delta slots that the requests needed, in %:
100 x the sum of ``own_ops`` over the sum of ``cap`` x ``padded`` x
``replays`` of the window's two-phase point and diff ``window_delta``
spans (program span).  Each of a group's ``padded`` requests replays
all ``cap`` slots of its union window, ``replays`` times; it needs only
the ``own_ops`` logged ops of its own windows."""


def read(ctx):
    spans = [e["args"] for e in ctx.spans or ()
             if e["name"] == "window_delta" and "own_ops" in e["args"]]
    slots = sum(a["cap"] * a["padded"] * a["replays"] for a in spans)
    if not slots:
        return None
    return 100.0 * sum(a["own_ops"] for a in spans) / slots
