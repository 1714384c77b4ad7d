"""The least time the chip could take for causal attention, forward and
backward, at the cell's shapes (operations and bytes from
``costs.causal_attention_train_cost`` through the family, against the peak
table; recomputed forwards not counted) over ``flash_ms``. At GPT-2
medium's heads of 64 and 1024 positions the compute bound holds, barely:
300 FLOP a byte against the v5e's ridge of 240."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "step_ms"


def read(ctx):
    import costs
    import xplane

    cost = ctx["family"].kernel_costs(
        ctx["cell"]["config_spec"], ctx["cell"]["job"]).get("flash")
    if cost is None or not ctx["loop"].get("mosaic_calls"):
        return None
    ms = xplane.per_step_ms(ctx["trace"],
                            lambda r: r["category_ns"].get("mosaic", 0))
    if not ms:
        return None
    least_s, _bound = costs.roofline_seconds(*cost, ctx["peak"])
    return 100.0 * least_s * 1e3 / ms
