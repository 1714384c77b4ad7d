"""Device-aware dispatch: one table of path choices, one override.

Some path choices invert across backends (the Pallas flash kernel is
compiled on a TPU and interpreted on a CPU), so every such choice goes
through :func:`choice`: a call site names its decision
(``"moe_dispatch"``), its candidates, and a key built by
:func:`decision_key` from ``(device_kind, shape-bucket, dtype)``, and
gets the ``CHAINERMN_TPU_AUTOTUNE_FORCE`` override (comma-separated,
e.g. ``moe_dispatch=einsum,attention=xla``) if the environment names
the decision, else :data:`DEFAULT_TABLE`'s entry for the key's device
class. No timing is persisted or taken at a call site; a run prints
what each site resolved from :func:`decisions_taken`.
"""

from chainermn_tpu.tuning.registry import (
    DEFAULT_TABLE,
    choice,
    current_device_kind,
    decision_key,
    decisions_taken,
    device_class,
    reset_decisions,
    shape_bucket,
)

__all__ = [
    "DEFAULT_TABLE",
    "choice",
    "current_device_kind",
    "decision_key",
    "decisions_taken",
    "device_class",
    "reset_decisions",
    "shape_bucket",
]
