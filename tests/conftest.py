import jax
import pytest


@pytest.fixture(scope="session")
def mesh1():
    """Trivial (1,1) mesh — exercises the sharded code paths on one device.

    (Real multi-device partitioning is tested in tests/test_multidevice.py
    via a subprocess with --xla_force_host_platform_device_count, so the
    main process keeps the default 1-device view per the project brief.)"""
    return jax.make_mesh(
        (1, 1), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2
    )
