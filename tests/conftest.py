"""Test configuration: force an 8-device virtual CPU mesh (SURVEY.md §4
lesson: every distributed test must run without TPU hardware, the way the
reference's tests run under `horovodrun -np 2` on one CPU machine).

The suite is a CPU program wherever it runs: the platform is forced
through jax's config before any backend exists, so a machine with a
chip never has it opened by pytest.
"""
import os

# For any worker subprocesses spawned by tests (they inherit the
# platform, as the launcher's workers do).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
assert len(jax.devices()) == 8 and jax.devices()[0].platform == "cpu"

import pytest


def pytest_sessionstart(session):
    """Offline-descope tripwire: this environment cannot install
    pyspark (no network), so tests/test_spark.py validates against a
    barrier-semantics mock and README documents the descope. The moment
    this repo lands somewhere pyspark IS importable, that caveat must
    turn into a red test — not a silently stale claim. (mxnet needs no
    tripwire: its tests importorskip and auto-unskip against the real
    package.) Set HOROVOD_REAL_SPARK_VALIDATED=1 once real-Spark runs
    are wired to acknowledge."""
    import importlib.util

    if (importlib.util.find_spec("pyspark") is not None
            and not os.environ.get("HOROVOD_REAL_SPARK_VALIDATED")):
        raise pytest.UsageError(
            "pyspark is importable, but tests/test_spark.py and "
            "tests/test_framework_estimators.py still validate against "
            "the mock barrier layer only. Run the estimators/runner "
            "against real Spark and set HOROVOD_REAL_SPARK_VALIDATED=1 "
            "(see README 'offline descopes')."
        )


@pytest.fixture
def hvd_mesh():
    """Fresh mesh-mode init for a test, torn down after."""
    import horovod_tpu as hvd

    hvd.shutdown()
    hvd.init()
    yield hvd
    hvd.shutdown()


@pytest.fixture()
def hvd_single():
    """Fresh SIZE-1 mesh-mode world (single device), torn down after —
    for tests of single-process semantics that must not inherit a
    leaked full-mesh world from an earlier in-process test."""
    import jax

    import horovod_tpu as hvd

    hvd.shutdown()
    hvd.init(devices=jax.devices()[:1])
    yield hvd
    hvd.shutdown()
