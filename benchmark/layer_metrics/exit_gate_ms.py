"""Per step and device, self time of the traced ops inside the program's
``exit_gate`` scope, forward and backward: a looped model's exit gate, the
exit distribution over its passes and that distribution's entropy. The
scope's name is spelled here, as ``scopes.py`` spells the others: ``None``
where the run's step carries no such name."""

LAYER = "model"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import scopes

    return scopes.scope_ms(ctx, ("exit_gate",))
