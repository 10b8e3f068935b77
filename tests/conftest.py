"""Test harness: single-host JAX on a virtual 8-device CPU mesh.

The reference tests all "distributed" logic on a multi-threaded local
SparkSession (``local[*]``, reference:
core/test/base/src/main/scala/SparkSessionFactory.scala:39-51); the analog
here is the JAX CPU backend with 8 virtual devices via
``--xla_force_host_platform_device_count``, so every sharding/collective
path compiles and executes without TPU hardware.
"""

import os

# must run before jax initializes: tier-1 is a CPU suite wherever it runs,
# a chip machine included (jax.config.update below covers a jax that some
# plugin imported before this file)
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


def thread_names(*prefixes):
    """Live threads whose names start with one of ``prefixes``."""
    import threading
    return [t.name for t in threading.enumerate()
            if any(t.name.startswith(p) for p in prefixes)]


def assert_no_leaked_threads(*prefixes, timeout=5.0):
    """Assert that no thread named with one of ``prefixes`` survives,
    polling up to ``timeout`` — shutdown paths signal their workers
    before join returns, so a just-closed subsystem may need a few ms
    to finish unwinding. The one leak assertion every suite shares
    (serve lanes, train loaders, beacons, obs samplers); prefix
    allowlisting keeps it scoped to the subsystem under test instead
    of flaking on pytest's own machinery threads."""
    import time
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not thread_names(*prefixes):
            return
        time.sleep(0.02)
    raise AssertionError(
        f"leaked threads (prefixes {prefixes}): {thread_names(*prefixes)}")


@pytest.fixture(name="assert_no_leaked_threads")
def _assert_no_leaked_threads_fixture():
    return assert_no_leaked_threads


@pytest.fixture()
def pallas_interpret():
    """Run Pallas TPU kernels through jax's interpreter for the test.

    The kernel wrappers never pick interpret mode themselves (on the
    chip that would hide a kernel the compiler refuses), so a CPU test
    that executes a kernel body asks for it here, explicitly. Modules
    opt in with ``pytestmark = pytest.mark.usefixtures(...)``."""
    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():
        yield


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture()
def tmp_save_path(tmp_path):
    return str(tmp_path / "stage")


def make_tabular(n=100, seed=0):
    """Small mixed-type table used across suites (GenerateDataset analog)."""
    from mmlspark_tpu.data.table import DataTable
    r = np.random.default_rng(seed)
    return DataTable({
        "num": r.normal(size=n),
        "int": r.integers(0, 10, size=n),
        "cat": [["red", "green", "blue"][i % 3] for i in range(n)],
        "text": [f"word{i % 7} tok{i % 3}" for i in range(n)],
        "label": (r.random(n) > 0.5).astype(np.int64),
    })
