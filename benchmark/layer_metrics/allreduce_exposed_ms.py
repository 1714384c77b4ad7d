"""Per step and device, the part of the collectives' time in flight in
which no other op ran on that device: what the reduction adds to the
step."""

LAYER = "gradient reduction"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import xplane

    return xplane.per_step_ms(ctx["trace"],
                              lambda r: r["collective_exposed_ns"])
