"""Per step and device, self time of the traced ops inside the program's
``loop_stack`` scope, forward, backward and recomputed: the passes of a
looped model's layer stack over one set of weights, each closed by the
final norm. The scope's name is spelled here, as ``scopes.py`` spells the
others: ``None`` where the run's step carries no such name (the parent of
the PR that brought it, or a cell whose model is not looped)."""

LAYER = "model"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import scopes

    return scopes.scope_ms(ctx, ("loop_stack",))
