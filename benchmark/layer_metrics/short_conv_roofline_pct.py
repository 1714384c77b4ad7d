"""The least time the chip could take for the gated short convolutions
between their projections, forward and backward (operations and bytes
from ``hybrid_costs.short_conv_train_cost`` through the family's
``kernel_costs()["short_conv"]``, against the peak table), over
``short_conv_ms``. The memory bound holds (11 tensors of ``[tokens, d]``
bf16 a layer against 24 flops an element). The denominator is the whole
scope, so whatever XLA keeps with the chain counts against it; where XLA
fuses part of the chain into a projection's matmul, that part's time
leaves the scope and the share reads high."""

LAYER = "model"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import costs
    import scopes

    cost = ctx["family"].kernel_costs(
        ctx["cell"]["config_spec"], ctx["cell"]["job"]).get("short_conv")
    ms = scopes.scope_ms(ctx, ("short_conv",))
    if cost is None or not ms:
        return None
    least_s, _bound = costs.roofline_seconds(*cost, ctx["peak"])
    return 100.0 * least_s * 1e3 / ms
