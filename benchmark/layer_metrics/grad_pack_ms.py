"""Per step and device, self time of the traced ops inside the program's
``grad_reduce`` scope that are not collectives: the casts to and from the
wire dtype, packing into buckets and scaling that the gradient reduction
does round the wire (``allreduce_ms`` has the wire itself)."""

LAYER = "gradient reduction"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import scopes

    return scopes.scope_ms(ctx, (scopes.GRAD_REDUCE,),
                           not_category="collective")
