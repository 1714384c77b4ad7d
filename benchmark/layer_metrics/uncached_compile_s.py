"""Backend seconds of the programs of the run that were not loaded from the
persistent cache: ``jax_backend_compile_seconds_total`` of the programs
with no ``compile_cache_hits_total``. On a first run that is every compile;
in a cached run it is what the cache's thresholds (compile time, entry
size) leave out, paid again every run."""

LAYER = "entry and compile cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    import setup_counters

    return setup_counters.uncached_compile_s(ctx)
