"""The device scopes of the program's dropless mixture-of-experts layer,
spelled again here as ``scopes.py`` spells the others (a rename in the
program shows as a missing metric), and the readers over them. Each
returns ``None`` where the run's step carries no such name (the parent of
the PR that brought them, or a cell without experts)."""

from __future__ import annotations

import scopes

MOE_ROUTE = "moe_route"
MOE_DISPATCH = "moe_dispatch"
MOE_EXPERTS = "moe_experts"
MOE_COMBINE = "moe_combine"


def experts_ms(ctx):
    """Per step and device, self time of the traced ops inside
    ``moe_experts``, forward and backward: the grouped matmuls, the SiLU
    gate and the casts XLA keeps with them."""
    return scopes.scope_ms(ctx, (MOE_EXPERTS,))


def dispatch_ms(ctx):
    """The same for ``moe_route`` + ``moe_dispatch`` + ``moe_combine``:
    everything of the layer that is not an expert."""
    parts = [scopes.scope_ms(ctx, (name,))
             for name in (MOE_ROUTE, MOE_DISPATCH, MOE_COMBINE)]
    if all(p is None for p in parts):
        return None
    return sum(p or 0.0 for p in parts)
