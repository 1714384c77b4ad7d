"""Seconds of the run spent reading, decompressing and loading executables
the persistent compilation cache held, every program of the process
together: the program's ``compile_cache_retrieval_seconds_total``. The part
of ``backend_compile_s`` that is no compiling."""

LAYER = "entry and compile cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    import setup_counters

    return setup_counters.cache_load_s(ctx)
