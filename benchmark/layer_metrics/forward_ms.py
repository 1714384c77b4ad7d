"""Per step and device, self time of the traced ops inside the program's
``loss_and_grad`` scope that JAX marks neither as transposed nor as
recomputed: the forward pass (``scopes.py``)."""

LAYER = "train step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import scopes

    return scopes.scope_ms(ctx, (scopes.LOSS_AND_GRAD,),
                           (scopes.BACKWARD, scopes.REMAT))
