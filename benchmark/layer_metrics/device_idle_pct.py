"""Share of the traced window in which no op ran on the device: 1 - union
of op intervals / window, mean over the cell's devices. The window runs
from the first to the last whole run of the step's module in the trace."""

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import xplane

    v = xplane.mean_over_devices(
        ctx["trace"], lambda r: 1.0 - r["busy_ns"] / r["window_ns"])
    return None if v is None else 100.0 * v
