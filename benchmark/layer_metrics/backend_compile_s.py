"""Seconds of the run inside the backend's compile-or-load, every program
of the process together: XLA and Mosaic compiling on a first run, loads
from the persistent cache afterwards (the program's
``jax_backend_compile_seconds_total``)."""

LAYER = "entry and compile cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    import scopes

    return scopes.counter(ctx, "jax_backend_compile_seconds_total")
