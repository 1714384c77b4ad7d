"""Native data-loader tests: C++ threaded prefetch must deliver exactly the
dataset's records (per epoch, shuffled, sharded) with correct field
decoding — the coverage the reference's iterator tests gave its data plane
(SURVEY.md section 4)."""

import numpy as np
import pytest

from chainermn_tpu.native.data_loader import (
    NativeDataLoader,
    write_fixed_records,
)

N, H = 64, 8


@pytest.fixture()
def dataset(tmp_path):
    rng = np.random.RandomState(0)
    images = rng.randint(0, 255, size=(N, H, H, 3)).astype(np.uint8)
    labels = np.arange(N, dtype=np.int32)  # label == record index
    path = str(tmp_path / "data.bin")
    write_fixed_records(path, images, labels)
    return path, images, labels


FIELDS = [
    ("image", np.uint8, (H, H, 3)),
    ("label", np.int32, ()),
]


def test_batches_decode_fields(dataset):
    path, images, labels = dataset
    dl = NativeDataLoader(path, FIELDS, batch_size=8, shuffle=False, threads=1)
    batch = next(dl)
    assert batch["image"].shape == (8, H, H, 3)
    assert batch["label"].shape == (8,)
    # label i identifies the record; image must be the matching one
    for img, lab in zip(batch["image"], batch["label"]):
        np.testing.assert_array_equal(img, images[lab])
    dl.close()


def test_epoch_covers_every_record_once(dataset):
    path, _, _ = dataset
    dl = NativeDataLoader(
        path, FIELDS, batch_size=8, shuffle=True, threads=3, seed=7
    )
    assert dl.batches_per_epoch == N // 8
    # Workers may interleave batches across the epoch boundary; group by
    # the batch's epoch tag and account for epoch 0 exactly. The bound is
    # generous (20 epochs of nexts): under full-suite CPU contention a
    # worker holding one epoch-0 batch can be starved for several epochs of
    # other workers' output before the scheduler runs it (observed flake at
    # a 3-epoch bound).
    seen = []
    epoch0_batches = 0
    for _ in range(20 * dl.batches_per_epoch):
        batch = next(dl)
        if dl.epoch == 0:
            seen.extend(batch["label"].tolist())
            epoch0_batches += 1
        if epoch0_batches == dl.batches_per_epoch:
            break
    dl.close()
    assert sorted(seen) == list(range(N))


def test_sharding(dataset):
    path, _, _ = dataset
    dl = NativeDataLoader(
        path, FIELDS, batch_size=4, shuffle=True, shard=(16, 32), threads=2
    )
    assert dl.num_records == 16
    labels = set()
    epoch0 = 0
    for _ in range(3 * dl.batches_per_epoch):
        batch = next(dl)
        if dl.epoch == 0:
            labels.update(batch["label"].tolist())
            epoch0 += 1
        if epoch0 == dl.batches_per_epoch:
            break
    dl.close()
    assert labels == set(range(16, 32))


def test_shuffle_deterministic_by_seed(dataset):
    path, _, _ = dataset

    def first_epoch(seed):
        dl = NativeDataLoader(
            path, FIELDS, batch_size=8, shuffle=True, seed=seed, threads=1
        )
        out = []
        for _ in range(dl.batches_per_epoch):
            out.extend(next(dl)["label"].tolist())
        dl.close()
        return out

    assert first_epoch(3) == first_epoch(3)
    assert first_epoch(3) != first_epoch(4)


def test_open_rejects_bad_record_size(dataset):
    path, _, _ = dataset
    with pytest.raises(RuntimeError, match="dl_open failed"):
        NativeDataLoader(path, [("x", np.uint8, (9,))], batch_size=4)

def test_bench_native_loop_child_mode(tmp_path):
    """``bench.py --run native-loop`` (the fresh-process end-to-end input
    benchmark child) runs loader → prefetch_to_device → jitted train step
    and prints a wall-time JSON line; its timed region holds no
    device→host transfer until the one sync that ends it
    (docs/benchmarks.md, input-pipeline section)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    try:
        from _driver_env import cpu_scrubbed_env
    finally:
        sys.path.pop(0)

    # Match bench._resnet_setup(on_accel=False) INSIDE THE CHILD: hw=32,
    # batch = 8 * mesh size, where the child's mesh is pinned to 8 by
    # cpu_scrubbed_env(8) below — NOT this process's device count (which
    # an externally-set XLA_FLAGS could make different).
    hw = 32
    batch = 8 * 8
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, size=(batch * 3, hw, hw, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, size=(batch * 3,)).astype(np.int32)
    path = str(tmp_path / "records.bin")
    write_fixed_records(path, images, labels)

    env = cpu_scrubbed_env(8, cache_dir=os.path.join(repo, ".jax_cache"))
    env.update(
        CMN_NATIVE_STEPS="2",
        CMN_NATIVE_RECORDS=path,
        CMN_NATIVE_HW=str(hw),
        CMN_NATIVE_BATCH=str(batch),
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"), "--run",
         "native-loop"],
        env=env, cwd=repo, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-1500:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
    out = json.loads(line)
    assert out["steps"] == 2
    assert out["batch"] == batch
    assert out["wall_s"] > 0
