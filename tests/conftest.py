"""Test harness configuration.

The reference simulated "multi-node" with N MPI processes on one host
(``mpiexec -n 2 pytest ...``, SURVEY.md section 4). The TPU-native analog is
a single process with N virtual host-platform devices: set
``--xla_force_host_platform_device_count=8`` *before* JAX initialises, and
build meshes from ``jax.devices('cpu')`` (NaiveCommunicator does this) so
tests are hermetic on any machine, TPU present or not.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
import jax  # noqa: E402
import pytest  # noqa: E402

# Hermeticity for the observability recorder: a developer
# shell (or a capture-script run) exporting CHAINERMN_TPU_TRACE must not
# make the suite write trace files — tests that need a recorder enable
# one explicitly (tests/test_trace.py).
os.environ.pop("CHAINERMN_TPU_TRACE", None)
os.environ.pop("CHAINERMN_TPU_TRACE_SYNC", None)
# ...and for the live telemetry plane (ISSUE 6): an exported metrics
# port would make every Trainer.run/Scheduler construction in the suite
# spawn an HTTP listener, and a hang-dump threshold would arm watchdog
# threads that write hang_dump_*.json into the repo — tests that need
# them start exporter/watchdog explicitly (tests/test_metrics.py).
os.environ.pop("CHAINERMN_TPU_METRICS_PORT", None)
os.environ.pop("CHAINERMN_TPU_HANG_DUMP_S", None)
os.environ.pop("CHAINERMN_TPU_HANG_DUMP_DIR", None)

# The suite is CPU-mesh-only by design: pin the platform at the config
# level, before the first backend init, so it stays hermetic whatever
# JAX_PLATFORMS the calling shell exported (a chip machine defaults to
# the TPU).
jax.config.update("jax_platforms", "cpu")

# Default eager/jit computations to the CPU backend: reference values in
# tests must use the same arithmetic as the CPU-mesh distributed versions
# (the real TPU's default bf16 matmul precision would otherwise skew
# eager-computed expectations by ~1e-3).
jax.config.update("jax_default_device", jax.devices("cpu")[0])


def load_example(*rel):
    """Load an example module by FILE PATH. A site-packages regular
    package named ``examples`` shadows the repo's namespace portions for
    any subdirectory both define (observed: ``examples.transformer``),
    so package imports are unreliable for examples — use this instead."""
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "examples", *rel,
    )
    spec = importlib.util.spec_from_file_location(rel[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices("cpu")
    assert len(devs) >= 8, "conftest must set device count before jax import"
    return devs[:8]


@pytest.fixture(scope="session")
def comm():
    """The canonical 8-slot test communicator (CPU mesh)."""
    from chainermn_tpu import create_communicator

    return create_communicator("naive")
