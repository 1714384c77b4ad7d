"""Seconds of the backend's compile-or-load of the train step's program: XLA
and Mosaic compiling it on a first run, its load from the persistent cache
afterwards (the program's ``jax_backend_compile_seconds_total`` under
``program="local_step"``)."""

LAYER = "entry and compile cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    import setup_counters

    return setup_counters.step_backend_s(ctx)
