"""The command itself: it refuses a CPU without printing a metric, and
through the harness's test-only door (``run.run_cell`` with CPU devices, a
made-up peak and a throw-away root) the whole path runs at a tiny size,
on one device and data-parallel on four virtual ones.

The throw-away root (``conftest.py``: ``added``) is also the demonstration
that new cells, configurations, mixes and a per-layer metric need only new
files and new entries of ``BENCHMARK.json``: nothing under ``benchmark/``
is edited, the files are written beside it, and ``test_contract.py`` holds
the extended benchmark to the same rules as the committed one.
"""

import os
import subprocess
import sys
import time

import jax

import run
import spec

FAKE_PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}  # no chip's


def _run(added, cell, trace, n_devices):
    roots, benchmark = added
    return run.run_cell(
        cell, seed=3, seconds=0.5, trace=trace,
        devices=jax.devices()[:max(n_devices, 1)], peak=FAKE_PEAK,
        roots=roots, benchmark=benchmark, t0=time.perf_counter())


def test_tiny_lm_cell_end_to_end(added):
    line = _run(added, "tiny-lm", False, 1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    assert set(line["metrics"]) == {"tokens_per_s", "step_ms", "mfu_pct",
                                    "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"  # never printed as a result


def test_tiny_dp4_cell_traced_reads_the_new_metric(added):
    line = _run(added, "tiny-lm-dp4", True, 4)
    assert line["correct"] is True and line["device"]["used"] == 4
    got = set(line["metrics"])
    assert {"compile_s", "input_wait_ms", "peak_hbm_gb",
            "steps_done"} <= got
    assert line["metrics"]["steps_done"]["value"] == line["attempted"]
    # a CPU trace has no device plane: the trace's readers return nothing
    # and the harness leaves their metrics out
    assert not got & {"matmul_ms", "flash_ms", "device_idle_pct",
                      "allreduce_ms", "allreduce_exposed_ms"}
    assert "busy_s" not in line["device"]


def test_tiny_resnet_cell_end_to_end(added):
    line = _run(added, "tiny-resnet", False, 1)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"images_per_s", "step_ms", "mfu_pct",
                                    "setup_s"}


def test_resnet_trains_the_programs_own_initialisation(added):
    """The cell trains what the program initialises (each block's last
    BatchNorm scale zero); only the gradient comparison's copy has those
    scales drawn, and nothing else differs."""
    import numpy as np

    roots, _ = added
    config = roots.json("configs", "tiny-resnet.json")
    fam = roots.module("families", "resnet").build(
        config, {"per_chip_batch": 8, "remat": "none"})
    params, _, check = fam.init(3)
    lo, hi = config["assumed"]["last_bn_scale"]
    drawn = 0
    for (path, own), alt in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree.leaves(check)):
        own, alt = np.asarray(own), np.asarray(alt)
        if jax.tree_util.keystr(path).endswith(
                "['MultiNodeBatchNormalization_2']['scale']"):
            assert (own == 0).all() and (alt >= lo).all() and (alt <= hi).all()
            drawn += 1
        else:
            assert (own == alt).all()
    assert drawn == sum(config["stage_sizes"])


def test_a_wrong_gradient_reduction_is_caught(added, monkeypatch):
    """A chip's contribution left out of the mean fails check (c)."""
    import chainermn_tpu.optimizers as opt

    real = opt.allreduce_gradients

    def lossy(grads, comm=None, **kw):
        from jax import lax
        import jax.numpy as jnp

        if comm is None:  # the program's own calls: left alone
            return real(grads, comm, **kw)
        keep = (lax.axis_index(comm.grad_axes[0]) != 0).astype(jnp.float32)
        return real(jax.tree.map(lambda g: g * keep, grads), comm, **kw)

    monkeypatch.setattr(opt, "allreduce_gradients", lossy)
    line = _run(added, "tiny-lm-dp4", False, 4)
    assert line["correct"] is False


def test_on_a_cpu_the_command_exits_nonzero_and_prints_no_metric():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, os.path.join(spec.HERE, "run.py"), "--workload",
         "gpt2m-podshare-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert "metrics" not in out.stdout and "correct" not in out.stdout


def test_without_the_program_the_command_exits_nonzero(tmp_path):
    import shutil

    shutil.copy(os.path.join(spec.CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "gpt2m-podshare-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and "no program to measure" in out.stderr
    assert "metrics" not in out.stdout
