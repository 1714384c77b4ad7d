"""Per step and device, self time of the traced ops inside the program's
``lm_head`` scope, forward, backward and recomputed: the chunked loop of
the fused head and what XLA keeps with it."""

LAYER = "model"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import scopes

    return scopes.scope_ms(ctx, (scopes.LM_HEAD,))
