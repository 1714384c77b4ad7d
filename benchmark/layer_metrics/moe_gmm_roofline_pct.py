"""The least time the chip could take for the experts' grouped matmuls,
forward and both gradients (operations and bytes from
``moe_costs.gated_experts_train_cost`` through the family's
``kernel_costs()["moe_gmm"]``, against the peak table), over
``moe_experts_ms``. At OLMoE's shapes the compute bound holds (4.95 TFLOP
against 8.9 GB a step). The denominator is the whole scope, so the SiLU
gate and the weights' casts count against the kernels."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import costs
    import moe_scopes

    cost = ctx["family"].kernel_costs(
        ctx["cell"]["config_spec"], ctx["cell"]["job"]).get("moe_gmm")
    ms = moe_scopes.experts_ms(ctx)
    if cost is None or not ms:
        return None
    least_s, _bound = costs.roofline_seconds(*cost, ctx["peak"])
    return 100.0 * least_s * 1e3 / ms
