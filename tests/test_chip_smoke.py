"""``chip_smoke.py`` off the chip: the script refuses anything but a TPU,
and its body — the library's front door from communicator to train step —
runs at a toy size on the CPU mesh."""

import os
import subprocess
import sys

import chip_smoke

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_script_refuses_a_cpu_and_names_it():
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=_REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "platform: cpu" in proc.stderr
    assert proc.stdout == ""  # no result line without a chip


def test_body_trains_a_toy_config_on_the_cpu_mesh():
    r = chip_smoke.train_smoke(
        num_layers=2, d_model=64, num_heads=4, d_ff=128, vocab_size=512,
        seq_len=128, per_chip_batch=2, head_chunks=2, warm_steps=1,
    )
    # cold + settle + one timed step each way; train_smoke itself checks
    # they are finite and falling, that every device holds a batch shard
    # and that the 8-device step all-reduces
    assert len(r["losses"]) == 4
    assert r["all_reduces"] > 0
    # off the chip the kernel is interpreted: what main() refuses to pass
    assert r["kernel_interpreted"] and r["mosaic_calls"] == 0
    # the decision log is process-wide: the smoke's one tuned site is in it
    assert "allreduce_bucket_mb" in [d["name"] for d in r["decisions"]]
