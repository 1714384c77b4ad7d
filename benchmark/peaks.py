"""Published peaks of one chip, keyed by ``device_kind``.

Only rows whose source is written beside them. A kind that is not here is
an error, never a default: a utilisation against a guessed peak is worse
than none. (The idea is ``bench.py:_peak_lookup``'s; that table keeps rows
without a source and is not read here.)
"""

from __future__ import annotations

#: substring of ``device_kind`` (lower case) -> peaks of one chip.
#: Source: Google Cloud documentation, "TPU v5e" system architecture:
#: 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of
#: inter-chip interconnect. JAX reports this chip as "TPU v5 lite".
_V5E = {
    "bf16_flops": 197e12,
    "hbm_bytes_per_s": 819e9,
    "ici_bits_per_s": 1600e9,
    "hbm_bytes": 16e9,
    "source": 'Google Cloud documentation, "TPU v5e"',
}
PEAKS = {"v5 lite": _V5E, "v5e": _V5E}


def lookup(device_kind: str, table: dict | None = None) -> dict:
    kind = device_kind.lower()
    for sub, row in (PEAKS if table is None else table).items():
        if sub in kind:
            return row
    raise KeyError(
        f"no published peak for device kind {device_kind!r}: add a row "
        "with its source to a peak table; the benchmark never guesses one"
    )
