"""Peak device memory of the compiled step from XLA's buffer assignment:
arguments + outputs + temporaries - aliased (donated) bytes, in GB of
1e9. The allocator's ``peak_bytes_in_use`` does not see a program's
temporaries on this runtime (PERF.md, PR 21)."""

LAYER = "train step"
UNIT = "GB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "step_ms"


def read(ctx):
    nbytes = ctx.get("step_memory_bytes")
    return None if nbytes is None else nbytes / 1e9
