"""Per step and device, self time of the traced ops inside the program's
``moe_shared`` scope, forward, backward and recomputed: the shared expert
every token passes beside the routed ones: its matmuls (gate|up as one,
down) and the SiLU gate. The scope's name is spelled here, as
``scopes.py`` spells the others: ``None`` where the run's step carries no
such name (the parent of the PR that brought it, or a cell without a
shared expert)."""

LAYER = "model"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"

MOE_SHARED = "moe_shared"


def read(ctx):
    import scopes

    return scopes.scope_ms(ctx, (MOE_SHARED,))
