"""Per step and device, device time of the ops whose HLO holds a ``dot``
or a ``convolution`` (fusions by what they call; the compiled step's text
says which)."""

LAYER = "model"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import xplane

    return xplane.per_step_ms(ctx["trace"],
                              lambda r: r["category_ns"].get("matmul", 0))
