"""Per step and device, self time of the traced ops inside the program's
``bd_attention`` scope, forward, backward and recomputed: a block-
diffusion model's masked attention over ``[x ; x~]`` between its
projections: the flash kernels' clean-on-clean and noised-on-clean
calls, the in-block part and the merge of the noised rows' partials by
their log-sum-exps. The scope's name is spelled here, as ``scopes.py``
spells the others: ``None`` where the run's step carries no such name
(the parent of the PR that brought it, or a cell of another family)."""

LAYER = "model"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"

BD_ATTENTION = "bd_attention"


def read(ctx):
    import scopes

    return scopes.scope_ms(ctx, (BD_ATTENTION,))
