"""Per step and device, the time a collective was in flight (from the
start of an asynchronous ``-start`` to the end of its ``-done``; a
synchronous one for its duration), overlapping flights counted once."""

LAYER = "gradient reduction"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import xplane

    return xplane.per_step_ms(ctx["trace"],
                              lambda r: r["collective_flight_ns"])
