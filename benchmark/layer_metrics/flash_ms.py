"""Per step and device, device time of the Mosaic custom calls
(``tpu_custom_call``): the flash attention forward and backward kernels
are the only ones in a language-model step."""

LAYER = "kernels"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import xplane

    if not ctx["loop"].get("mosaic_calls"):
        return None  # no kernel in this step: nothing to read
    return xplane.per_step_ms(ctx["trace"],
                              lambda r: r["category_ns"].get("mosaic", 0))
