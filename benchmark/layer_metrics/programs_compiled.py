"""Programs of the run the backend compiled, not loaded: the program's
``programs_compiled_total`` less ``compile_cache_hits_total``. In a cached
run these are the programs the cache's policy does not keep."""

LAYER = "entry and compile cache"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    import scopes

    through = scopes.counter(ctx, "programs_compiled_total")
    if through is None:
        return None
    return through - (scopes.counter(ctx, "compile_cache_hits_total") or 0.0)
