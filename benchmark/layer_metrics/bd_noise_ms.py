"""Per step and device, self time of the traced ops inside the program's
``bd_noise`` scope: a block-diffusion step's draw of a noise level a
block and a mask a token, the noised copy, the concatenation ``[x ; x~]``
and its positions, made inside the compiled step. ``None`` where the
run's step carries no such name."""

LAYER = "model"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"

BD_NOISE = "bd_noise"


def read(ctx):
    import scopes

    return scopes.scope_ms(ctx, (BD_NOISE,))
