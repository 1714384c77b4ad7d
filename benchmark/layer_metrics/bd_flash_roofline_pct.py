"""The least time the chip could take for the masked attention over
``[x ; x~]``, forward and backward, over the mask's support alone
(operations and bytes from ``bd_costs.bd_attention_train_cost`` through
the family's ``kernel_costs()["bd_flash"]``, against the peak table;
recomputed forwards not counted), over the time of the three flash
kernels under the ``bd_attention`` scope (the Mosaic calls there; the
in-block part and the merge are XLA's and count in ``bd_attention_ms``).
At heads of 128 and 8192 positions the compute bound holds."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import costs
    import scopes

    cost = ctx["family"].kernel_costs(
        ctx["cell"]["config_spec"], ctx["cell"]["job"]).get("bd_flash")
    ms = scopes.scope_ms(ctx, ("bd_attention",), category="mosaic")
    if cost is None or not ms:
        return None
    least_s, _bound = costs.roofline_seconds(*cost, ctx["peak"])
    return 100.0 * least_s * 1e3 / ms
