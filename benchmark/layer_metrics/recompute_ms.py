"""Per step and device, self time of the traced ops JAX marks as
recomputed (``rematted_computation``): the forward work a ``remat`` policy
runs again inside the backward pass, the fused head's chunks included."""

LAYER = "train step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import scopes

    return scopes.scope_ms(ctx, (scopes.REMAT,))
