"""Per step and device, self time of the traced ops inside the program's
``short_conv`` scope, forward, backward and recomputed: what lies between
a gated short convolution's two projections (the split of the input
projection, the two gates and the depthwise causal convolution). The
projections are matmuls outside the scope (``matmul_ms``). The scope's
name is spelled here, as ``scopes.py`` spells the others: ``None`` where
the run's step carries no such name (the parent of the PR that brought it,
or a cell without such layers)."""

LAYER = "model"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"

SHORT_CONV = "short_conv"


def read(ctx):
    import scopes

    return scopes.scope_ms(ctx, (SHORT_CONV,))
