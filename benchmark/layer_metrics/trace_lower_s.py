"""Seconds of the run JAX spent tracing functions to jaxprs (nested traces
counted once) and lowering them to MLIR, every program of the process
together: the program's ``jax_trace_seconds_total`` and
``jax_lower_seconds_total``, counted from ``jax.monitoring`` events."""

LAYER = "entry and compile cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    import scopes

    traced = scopes.counter(ctx, "jax_trace_seconds_total")
    lowered = scopes.counter(ctx, "jax_lower_seconds_total")
    if traced is None and lowered is None:
        return None
    return (traced or 0.0) + (lowered or 0.0)
