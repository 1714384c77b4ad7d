"""Mean time a step of the window spent in ``next(batches)``: the feed
(the program's ``prefetch_to_device``) handing over a batch it has already
sent to the device, and sending the next."""

LAYER = "input feed"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "step_ms"


def read(ctx):
    waits = ctx["loop"].get("input_wait_s")
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
