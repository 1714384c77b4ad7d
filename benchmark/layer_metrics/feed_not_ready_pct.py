"""Share of the batches the feed handed over whose transfer to the device
had not finished at that moment (a leaf's ``is_ready()`` false), over the
whole run: the program's ``feed_not_ready_total`` over
``feed_batches_total``."""

LAYER = "input feed"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "step_ms"


def read(ctx):
    import scopes

    batches = scopes.counter(ctx, "feed_batches_total")
    if not batches:
        return None
    return 100.0 * (scopes.counter(ctx, "feed_not_ready_total") or 0.0) \
        / batches
