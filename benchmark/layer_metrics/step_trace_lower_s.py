"""Seconds JAX spent tracing the train step's program to a jaxpr (nested
traces counted once, under it) and lowering it to MLIR: the program's
``jax_trace_seconds_total`` and ``jax_lower_seconds_total`` under
``program="local_step"``, the label ``make_train_step``'s function carries.
With ``step_backend_s`` it should account for ``compile_s``, the host clock
round the same ``lower().compile()``. The reader also leaves the run's
table by program in the log (``setup_counters.say_table``)."""

LAYER = "entry and compile cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    import setup_counters

    setup_counters.say_table(ctx)
    return setup_counters.step_trace_lower_s(ctx)
