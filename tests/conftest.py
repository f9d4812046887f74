"""Test harness: an 8-device virtual CPU mesh.

The reference simulates clusters with local-mode Spark + multi-partition RDDs
(``src/test/scala/pipelines/LocalSparkContext.scala``, SURVEY.md §4.1). The
TPU-native equivalent: force the JAX CPU backend to expose 8 host devices so
every sharding/collective path is exercised by the unit tests exactly as it
would run on an 8-chip slice.

Must run before jax initializes a backend — conftest import time is safe as
long as no other conftest/plugin imports jax first.
"""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# the tests ARE the CPU mesh: ask for it explicitly (the one way to get
# the CPU — core/runtime.py), for this process and every child it spawns
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # registered here (and in pyproject) so -m multihost / --strict-markers
    # work: the multihost tests spawn REAL jax.distributed worker
    # processes and are the slowest part of the suite — filterable, and
    # they skip cleanly (worker exit 42) where the rig can't run them
    config.addinivalue_line(
        "markers",
        "multihost: spawns real multi-process jax.distributed workers "
        "(skips cleanly when the rig cannot join a 2-process runtime "
        "or hand out TCP ports)",
    )
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from tier-1 (-m 'not slow')",
    )


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def mesh8(devices):
    """8-way data-parallel mesh — the `local[4]`-with-partitions analog."""
    from keystone_tpu.parallel.mesh import create_mesh

    return create_mesh(data=8)


@pytest.fixture
def mesh4x2(devices):
    """4-way data x 2-way model mesh for block/model-parallel tests."""
    from keystone_tpu.parallel.mesh import create_mesh

    return create_mesh(data=4, model=2)


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def free_tcp_port_factory():
    """Self-contained port allocator for the multihost coordinator tests
    (no dependency on anyio's plugin fixtures): bind to port 0, read the
    OS-assigned port, close so the coordinator can bind it. A seen-set
    guards repeated calls in one test against the kernel handing the
    just-released port straight back."""
    import socket

    seen = set()

    def factory() -> int:
        while True:
            try:
                s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
                s.bind(("127.0.0.1", 0))
                port = s.getsockname()[1]
                s.close()
            except OSError as e:  # sandboxed rig with no loopback bind
                pytest.skip(f"no TCP ports available: {e!r}")
            if port not in seen:
                seen.add(port)
                return port

    return factory


@pytest.fixture
def free_tcp_port(free_tcp_port_factory):
    return free_tcp_port_factory()
