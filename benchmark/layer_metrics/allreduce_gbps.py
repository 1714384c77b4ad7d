"""Algorithm bandwidth of the gradient reduction in Gbit/s: the bytes one
device hands it each step (``wire_mb``) times 8 over the time its
collectives were in flight (``allreduce_ms``). No ring factor: an
all-reduce over n chips moves 2(n-1)/n times these bytes over each link,
so set it against the 1,600 Gbit/s of a v5e's interconnect with that in
mind."""

LAYER = "gradient reduction"
UNIT = "Gbit/s"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import scopes
    import xplane

    nbytes = scopes.counter(ctx, "grad_wire_bytes_per_step")
    flight_ms = xplane.per_step_ms(ctx["trace"],
                                   lambda r: r["collective_flight_ns"])
    if not nbytes or not flight_ms:
        return None
    return nbytes * 8 / (flight_ms * 1e-3) / 1e9
