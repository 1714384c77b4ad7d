"""MB (of 1e6 bytes) one device hands the gradient reduction each step, all
wire dtypes together: the program's ``grad_wire_bytes_per_step`` gauge,
written while the step is traced (0 where the reduction spans one
device)."""

LAYER = "gradient reduction"
UNIT = "MB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "step_ms"


def read(ctx):
    import scopes

    nbytes = scopes.counter(ctx, "grad_wire_bytes_per_step")
    return None if nbytes is None else nbytes / 1e6
