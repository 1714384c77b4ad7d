"""The decision registry: ``choice(name, candidates, key)``.

A decision resolves to the ``CHAINERMN_TPU_AUTOTUNE_FORCE`` override
(``name=winner,...``) when the environment names it, else to
:data:`DEFAULT_TABLE`'s entry for the key's device class. Nothing is
read from or written to disk and nothing is timed: ``choice`` is pure
Python over its arguments, that one variable and the table, so it is
safe inside a trace.

Every resolution is appended to a process-local decision log, so the
benchmark's loop, ``chip_smoke.py`` and ``dryrun_multichip`` can print
which path each site took.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

#: ``decision -> device class -> winner`` (``*`` = any class): what the
#: code does until a benchmark cell (``BENCHMARK.json``) has measured both
#: sides on the chip; then the loser and its option are deleted, or the
#: choice is made from something the code observes (ROADMAP D2). Each
#: comment names the sides and the cell that adjudicates the decision.
DEFAULT_TABLE: dict = {
    # Capacity-path MoE dispatch, index sort | dense [T, E, C] einsum: the
    # path D4b folds into the dropless one `olmoe-hostfill-1chip` runs.
    "moe_dispatch": {"cpu": "sort", "tpu": "sort", "*": "sort"},
    # Experts over an `expert` mesh axis (two all-to-alls a layer) | every
    # shard hosts every expert: R3's four-chip MoE cell.
    "expert_parallel": {"*": "off"},
    # Pallas flash kernel | XLA attention. A CPU interprets the kernel,
    # so `xla` there; every LM cell hands the kernel in: TPU side settled.
    "attention": {"cpu": "xla", "tpu": "flash", "*": "flash"},
    # The same under a sliding window: R2's windowed model.
    "attention_windowed": {"cpu": "xla", "tpu": "windowed", "*": "windowed"},
    # The gradient path's two: the cell is `gpt2m-podshare-dp4` (710 MB
    # over ICI). Wire f32|bf16|int8 where a caller asks for `auto`.
    "allreduce_wire": {"*": "bf16"},
    # MB a bucket of the packed schedules (`none` = one fused buffer).
    "allreduce_bucket_mb": {"*": "64"},
    # Serving, from here on: no cell yet; what names no other item
    # waits for R1's. Paged | dense per-slot KV cache.
    "decode_impl": {"*": "paged"},
    "kv_block_size": {"*": "64"},
    # XLA's gather + einsum | the fused Pallas kernel (ops/paged_decode.py),
    # which Mosaic refuses today (ROADMAP S8).
    "decode_attend_impl": {"*": "xla"},
    # Speculative tokens a tick; the payoff is the traffic's acceptance.
    "spec_tokens": {"*": "0"},
    # Cross-request KV prefix sharing; a miss costs host metadata only.
    "prefix_cache": {"*": "on"},
    "min_shared_blocks": {"*": "1"},
    # Prefill and decode on separate replicas | colocated: R5h.
    "cluster_disagg": {"*": "colocated"},
    # Prompt tokens prefilled a decode tick; 0 = monolithic prefill.
    "prefill_chunk": {"*": "0"},
    # Ring (n-1 ppermutes a layer) | Ulysses (all-to-alls; heads must
    # divide): R5d's long-context cell, with `prefill_seq_parallel`.
    "seq_attn_impl": {"*": "ring"},
    # Adapter rows gathered in the forward | one tenant merged into the
    # weights: no cell in sight (serving/adapters.py).
    "adapter_impl": {"*": "gather"},
    # Long-prompt prefill sharded over the replica's `model` partition.
    "prefill_seq_parallel": {"*": "off"},
}

_FORCE_ENV = "CHAINERMN_TPU_AUTOTUNE_FORCE"

#: process-local decision log: (name, key) -> record, insertion-ordered
_DECISIONS: dict = {}


def _forced() -> dict:
    out = {}
    for part in os.environ.get(_FORCE_ENV, "").split(","):
        if "=" in part:
            name, _, winner = part.partition("=")
            out[name.strip()] = winner.strip()
    return out


def current_device_kind() -> str:
    """``device_kind`` of the default backend's first device (``"cpu"``,
    ``"TPU v5 lite"``, ...); ``"unknown"`` when no backend is up. Call
    sites resolving at trace time always have a live backend."""
    try:
        import jax

        return jax.devices()[0].device_kind
    except Exception:
        return "unknown"


def device_class(device_kind: str) -> str:
    """Coarse class for table lookup: ``cpu`` / ``tpu`` / ``*``."""
    kind = (device_kind or "").lower()
    if "cpu" in kind:
        return "cpu"
    if "tpu" in kind or kind.startswith("v"):
        return "tpu"
    return "*"


def shape_bucket(shape: Sequence[int]) -> str:
    """Bucket each dim up to the next power of two, joined with ``x`` —
    nearby shapes share one key (and one line of the decision log)."""

    def bucket(d: int) -> int:
        d = int(d)
        if d < 1:
            raise ValueError(f"shape dims must be >= 1, got {d}")
        b = 1
        while b < d:
            b <<= 1
        return b

    return "x".join(str(bucket(d)) for d in shape)


def decision_key(
    device_kind: Optional[str] = None,
    shape: Optional[Sequence[int]] = None,
    dtype=None,
) -> str:
    """``"<device_kind>|<shape-bucket>|<dtype>"`` — the key a call
    site's decision is resolved and logged under. ``device_kind`` defaults to the
    live backend's; ``dtype`` accepts anything ``jnp.dtype`` does (or a
    plain string tag for non-dtype keys)."""
    kind = device_kind if device_kind is not None else current_device_kind()
    shape_s = shape_bucket(shape) if shape else "-"
    if dtype is None:
        dtype_s = "-"
    elif isinstance(dtype, str):
        dtype_s = dtype
    else:
        import numpy as np

        dtype_s = np.dtype(dtype).name
    return f"{kind}|{shape_s}|{dtype_s}"


def _record(name: str, key: str, winner: str, source: str) -> None:
    _DECISIONS[(name, key)] = {
        "name": name, "key": key, "winner": winner, "source": source,
    }
    # Every resolution also lands in the structured trace (when one is
    # active) as a ``dispatch`` event.
    try:
        from chainermn_tpu.observability import trace as _trace

        rec = _trace.active()
        if rec is not None:
            rec.event("dispatch", **_DECISIONS[(name, key)])
    except Exception:
        pass


def decisions_taken() -> list:
    """The decisions this process resolved, in first-resolution order —
    what the benchmark's loop and dryrun_multichip print."""
    return list(_DECISIONS.values())


def reset_decisions() -> None:
    """Clear the process-local decision log (test isolation)."""
    _DECISIONS.clear()


def _table_winner(name: str, key: str, candidates) -> str:
    tab = DEFAULT_TABLE.get(name, {})
    cls = device_class(key.split("|", 1)[0])
    winner = tab.get(cls) or tab.get("*")
    if winner in candidates:
        return winner
    return candidates[0]


def choice(name: str, candidates: Sequence[str], key: str) -> str:
    """Resolve decision ``name`` among ``candidates`` for ``key``: the
    forced override if the environment names the decision (a winner
    outside ``candidates`` is an error), else the table's entry for the
    key's device class."""
    if not candidates:
        raise ValueError(f"decision {name!r}: no candidates")
    forced = _forced().get(name)
    if forced is not None:
        if forced not in candidates:
            raise ValueError(
                f"{_FORCE_ENV} forces {name}={forced!r}, not one of "
                f"{tuple(candidates)}"
            )
        _record(name, key, forced, "forced")
        return forced
    winner = _table_winner(name, key, candidates)
    _record(name, key, winner, "table")
    return winner
