"""Test rig: single-process 8-device virtual CPU mesh (the JAX analog of the
reference's oversubscribed ``mpirun``, SURVEY.md §4 item 5).  Platform-forcing
mechanics live in tpu_radix_join/utils/platform.py."""

from tpu_radix_join.utils.platform import force_host_cpu_devices

force_host_cpu_devices(8, respect_existing=True)

import jax
import pytest

# no persistent compile cache in the suite, even where the environment sets
# JAX_COMPILATION_CACHE_DIR: tests/test_chip_compile.py's described-chip
# compiles would be written there but could not be read back without a chip
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture
def transfer_guard():
    """Arm ``jax.transfer_guard("disallow")`` for the test body: any
    implicit device<->host transfer raises.  The runtime twin of
    tools_lint.py's static sync-point rule — explicit readbacks through
    ``utils.hostsync.host_readback`` (jax.device_get) stay legal, so a
    test passing under this fixture proves the code path only syncs
    where it says it does.  Build inputs BEFORE requesting the fixture
    value's context (it is already armed when the test body runs), or
    pre-place them with jax.device_put, which is likewise explicit."""
    import jax

    with jax.transfer_guard("disallow"):
        yield
