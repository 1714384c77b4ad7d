"""Device-aware dispatch + persistent autotune cache.

The codebase used to hard-code path choices that INVERT across backends
(round-5 review): sort-based MoE dispatch is 167.8x the einsum path on
the CPU proxy but only 1.63x on TPU v5e at the production shape; the
flash kernel is 3.0x XLA attention on the chip but 0.56x under CPU
interpret mode; double buffering measures 0.752x on the proxy. A static
flag cannot be right on both backends — collective-algorithm and kernel
choice must be composed per device/topology (HiCCL, arxiv 2408.05962;
cross-replica update sharding, arxiv 2004.13336), so this package gives
every such choice one mechanism:

- :func:`choice` — the decision registry. A call site names its decision
  (``"moe_dispatch"``), its candidates, and a key built by
  :func:`decision_key` from ``(device_kind, shape-bucket, dtype)``;
  resolution order is forced-override -> persistent cache -> one-shot
  measurement (when callables are supplied and tracing is not active)
  -> deterministic per-device table.
- :mod:`~chainermn_tpu.tuning.measure` — the one-shot autotuner, using
  bench.py's median-of-n>=3 + spread discipline; a spread-dominated
  comparison falls back to the table instead of adopting noise.
- :mod:`~chainermn_tpu.tuning.cache` — the persistent JSON cache
  (``.autotune_cache.json``), seedable OFFLINE from a bench run's
  ``BENCH_DETAILS.json``
  (``python -m chainermn_tpu.tuning seed``) so on-chip sweep winners
  are adopted without re-measuring.

Call sites wired through the registry: MoE sort-vs-einsum dispatch
(:mod:`chainermn_tpu.parallel.moe`), attention variant selection
(:func:`chainermn_tpu.ops.attention.attention`), the allreduce wire
variant + bucket size (:mod:`chainermn_tpu.communicators`,
:mod:`chainermn_tpu.parallel.collectives`), and the double-buffering
advisory (:mod:`chainermn_tpu.optimizers`). ``bench.py`` and
``__graft_entry__.dryrun_multichip`` report which decision each site
took, so every capture shows its dispatch provenance.

Env knobs (documented in docs/benchmarks.md):

- ``CHAINERMN_TPU_AUTOTUNE`` — ``auto`` (default: cache, then measure
  when possible, then table), ``measure`` (same), ``table`` (never
  measure), ``off`` (ignore the cache too; pure table).
- ``CHAINERMN_TPU_AUTOTUNE_CACHE`` — cache file path (default:
  ``<repo>/.autotune_cache.json``).
- ``CHAINERMN_TPU_AUTOTUNE_FORCE`` — comma-separated hard overrides,
  e.g. ``moe_dispatch=einsum,attention=xla``.
"""

from chainermn_tpu.tuning.cache import (
    default_cache_path,
    load_cache,
    seed_from_bench_details,
    store_entry,
)
from chainermn_tpu.tuning.measure import measure_candidates, repeat_median
from chainermn_tpu.tuning.registry import (
    DEFAULT_TABLE,
    choice,
    current_device_kind,
    decision_key,
    decisions_summary,
    decisions_taken,
    device_class,
    record_measurement,
    reset_decisions,
    shape_bucket,
)

__all__ = [
    "DEFAULT_TABLE",
    "choice",
    "current_device_kind",
    "decision_key",
    "decisions_summary",
    "decisions_taken",
    "default_cache_path",
    "device_class",
    "load_cache",
    "measure_candidates",
    "record_measurement",
    "repeat_median",
    "reset_decisions",
    "seed_from_bench_details",
    "shape_bucket",
    "store_entry",
]
