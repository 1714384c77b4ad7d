"""Per step and device, self time of the traced ops inside the program's
``mla_attention`` scope, forward, backward and recomputed: a latent-
attention mixer whole, between the block's norm and the residual: its
four projections (queries, the down-projection to the latent and the
rope key, the up-projection to keys and values, the output), the latent's
norm, RoPE, the rope key's broadcast to the heads, the wrapper's
transpositions and the three flash kernels. The scope's name is spelled
here, as ``scopes.py`` spells the others: ``None`` where the run's step
carries no such name (the parent of the PR that brought it, or a cell of
another family)."""

LAYER = "model"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"

MLA_ATTENTION = "mla_attention"


def read(ctx):
    import scopes

    return scopes.scope_ms(ctx, (MLA_ATTENTION,))
