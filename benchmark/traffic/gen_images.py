"""Image batches for a classification job, from a seed: ``uint8`` NHWC
pixels and ``int32`` labels, as a decoded record file hands them over.
Casting and normalising happen on the device, in the loss function."""

from __future__ import annotations

import numpy as np


def pool(seed: int, params: dict, *, rows: int, image_size: int,
         channels: int, num_classes: int
         ) -> list[tuple[np.ndarray, np.ndarray]]:
    """``params['pool_batches']`` distinct ``(images, labels)`` batches.
    The same seed gives the same batches."""
    rng = np.random.default_rng([seed, 0x1A6E])
    shape = (rows, image_size, image_size, channels)
    return [
        (np.frombuffer(rng.bytes(int(np.prod(shape))), np.uint8).reshape(shape),
         rng.integers(0, num_classes, size=(rows,), dtype=np.int32))
        for _ in range(int(params["pool_batches"]))
    ]
