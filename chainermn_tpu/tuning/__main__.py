"""CLI for the autotune cache.

- ``python -m chainermn_tpu.tuning seed [DETAILS.json]`` — seed the
  persistent cache offline from a bench artifact (default:
  ``BENCH_DETAILS.json``), under the device kind it was measured on —
  a chip run's winners get adopted without re-measuring.
- ``python -m chainermn_tpu.tuning show`` — print the cache.

Both are jax-free (cache + seeding are plain JSON).
"""

from __future__ import annotations

import json
import sys

from chainermn_tpu.tuning.cache import (
    default_cache_path,
    load_cache,
    seed_from_bench_details,
)


def main(argv: list[str]) -> int:
    cmd = argv[0] if argv else "show"
    if cmd == "seed":
        details = argv[1] if len(argv) > 1 else None
        seeded = seed_from_bench_details(details)
        for line in seeded:
            print(f"seeded {line}")
        print(f"{len(seeded)} decisions -> {default_cache_path()}")
        return 0
    if cmd == "show":
        print(json.dumps(load_cache(), indent=1, sort_keys=True))
        return 0
    print(f"usage: python -m chainermn_tpu.tuning [seed [DETAILS]|show]; "
          f"got {cmd!r}", file=sys.stderr)
    return 2


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
