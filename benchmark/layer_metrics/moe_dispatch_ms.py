"""Per step and device, self time of the traced ops inside the program's
``moe_route``, ``moe_dispatch`` and ``moe_combine`` scopes, forward and
backward: the router, top-k and auxiliary losses, the sort by expert and
the gather into expert order, the weighted sum back (``moe_scopes.py``)."""

LAYER = "model"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import moe_scopes

    return moe_scopes.dispatch_ms(ctx)
