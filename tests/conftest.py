"""Test session setup: force JAX onto a virtual 8-device CPU mesh.

Mirrors the reference's strategy of testing distributed behavior on a single
machine (reference petastorm/tests/conftest.py) — here, multi-chip sharding is
exercised with ``--xla_force_host_platform_device_count=8`` so tests never need
TPU hardware.
"""
import os

# Must run before jax backends initialize anywhere.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Tests force the CPU whatever the host has: the env var covers subprocesses,
# and the explicit config update wins over anything else that set
# jax_platforms before the first backend initializes.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def synthetic_dataset(tmp_path_factory):
    """Session-scoped synthetic petastorm dataset (100 rows, 10 row groups)
    — parity with reference conftest.py:89."""
    from dataset_utils import create_test_dataset
    path = tmp_path_factory.mktemp("synthetic")
    url = f"file://{path}/ds"
    rows = create_test_dataset(url, num_rows=100, rows_per_row_group=10)
    return type("SyntheticDataset", (), {"url": url, "rows": rows,
                                         "path": f"{path}/ds"})


@pytest.fixture()
def spark_session():
    """A SparkSession for converter tests: the real pyspark when importable,
    the vendored :mod:`petastorm_tpu.test_util.minispark` local-mode engine
    otherwise (this image has no JVM). Either way the converter runs its real
    code paths — materialize, plan-hash cache, vector/precision conversion."""
    from petastorm_tpu.test_util import minispark
    minispark.install()
    from pyspark.sql import SparkSession
    spark = SparkSession.builder.master("local[2]") \
        .appName("petastorm-tpu-tests").getOrCreate()
    yield spark
    spark.stop()
    minispark.uninstall()


@pytest.fixture(scope="session")
def scalar_dataset(tmp_path_factory):
    """Session-scoped plain (non-petastorm) Parquet store — parity with
    reference conftest.py:101."""
    from dataset_utils import create_test_scalar_dataset
    path = tmp_path_factory.mktemp("scalar")
    url = f"file://{path}/ds"
    data = create_test_scalar_dataset(url, num_rows=100, row_group_size=10)
    return type("ScalarDataset", (), {"url": url, "data": data})


def pytest_collection_modifyitems(config, items):
    """Every process_pool test is also `slow`: spawning real worker
    interpreters costs 4-17s each on this 1-core host. The smoke tier
    (`pytest -m "not slow"`, `make smoke`) keeps thread/dummy coverage of
    the same code paths; the full run (`make test`) covers everything."""
    for item in items:
        if item.get_closest_marker("process_pool") is not None:
            item.add_marker(pytest.mark.slow)


# Test files whose rehearsals run ``chipbench.run --trace 1`` in the
# checkout's own state directory: they all write, and remove,
# ``<checkout>/.chipbench/trace`` (``chipbench/run.py``).
_TRACED_REHEARSALS = ("test_chipbench_moe.py", "test_chipbench_run.py",
                      "test_chipbench_spans.py")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    """One traced rehearsal at a time across xdist's workers: two at once
    remove each other's trace, and which tests meet depends on how many
    were collected before them. (A rehearsal with a state directory of its
    own, as ``test_chipbench_kanana2.py``'s, needs no turn.)"""
    if item.fspath.basename not in _TRACED_REHEARSALS:
        yield
        return
    import fcntl
    state_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".chipbench")
    os.makedirs(state_dir, exist_ok=True)
    with open(os.path.join(state_dir, "traced_rehearsal.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        yield
