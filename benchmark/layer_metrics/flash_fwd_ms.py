"""Per step and device, device time of the Mosaic custom calls inside the
program's ``flash_fwd`` scope: flash attention's forward kernel (run again under remat, and counted again).
With its two siblings it adds up to ``flash_ms``."""

LAYER = "kernels"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import scopes

    return scopes.scope_ms(ctx, (scopes.FLASH_FWD,), category="mosaic")
