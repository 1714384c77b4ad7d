"""Per step and device, self time of the traced ops inside the program's
``optimizer_update`` scope: the inner optimizer's sweep over parameters and
state, and ``optax.apply_updates``."""

LAYER = "train step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import scopes

    return scopes.scope_ms(ctx, (scopes.OPTIMIZER_UPDATE,))
