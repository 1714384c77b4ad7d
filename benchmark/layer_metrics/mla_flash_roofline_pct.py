"""The least time the chip could take for latent attention's causal
attention between its projections, forward and backward, at the published
key and value widths (operations and bytes from
``mla_costs.mla_attention_train_cost`` through the family's
``kernel_costs()["mla_flash"]``, against the peak table; recomputed
forwards not counted), over the time of the three flash kernels under the
``mla_attention`` scope (the Mosaic calls there; the projections, RoPE,
the rope key's broadcast and the wrapper's transpositions are XLA's and
count in ``mla_attention_ms``). At keys of 192, values of 128 and 8192
positions the compute bound holds."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import costs
    import scopes

    cost = ctx["family"].kernel_costs(
        ctx["cell"]["config_spec"], ctx["cell"]["job"]).get("mla_flash")
    ms = scopes.scope_ms(ctx, ("mla_attention",), category="mosaic")
    if cost is None or not ms:
        return None
    least_s, _bound = costs.roofline_seconds(*cost, ctx["peak"])
    return 100.0 * least_s * 1e3 / ms
