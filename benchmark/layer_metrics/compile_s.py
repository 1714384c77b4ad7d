"""Host clock around ``lower().compile()`` of the cell's step: a compile
on a checkout's first run, a load from the persistent cache afterwards
(tracing the step is paid either way)."""

LAYER = "entry and compile cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(ctx):
    return ctx["loop"].get("compile_s")
