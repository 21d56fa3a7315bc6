import jax
import pytest

# NOTE: do NOT set --xla_force_host_platform_device_count here; smoke tests
# and benches must see the real (1-device) host.  Multi-device tests fake
# devices in subprocesses of their own.

jax.config.update("jax_enable_x64", False)

try:  # property tests draw fresh examples: no example database on disk
    from hypothesis import settings

    settings.register_profile("repo", database=None)
    settings.load_profile("repo")
except ImportError:
    pass


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
