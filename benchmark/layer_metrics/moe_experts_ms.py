"""Per step and device, self time of the traced ops inside the program's
``moe_experts`` scope, forward and backward: the grouped matmuls of the
dropless mixture of experts, the SiLU gate between them and the casts of
the expert weights (``moe_scopes.py``)."""

LAYER = "kernels"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import moe_scopes

    return moe_scopes.experts_ms(ctx)
