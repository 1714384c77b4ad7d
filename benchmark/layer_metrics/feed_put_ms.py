"""Mean duration of the program's ``chainermn.feed.put`` span in the traced
stretch: ``prefetch_to_device`` handing one batch to ``jax.device_put``,
once a step. On the profiler's clock, read from the run's ``.xplane.pb``
(``scopes.py``)."""

LAYER = "input feed"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "step_ms"


def read(ctx):
    import scopes

    return scopes.span_mean_ms(ctx, scopes.FEED_PUT)
