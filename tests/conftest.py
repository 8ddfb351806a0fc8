import numpy as np
import pytest

import jax

# Tests run on the single host CPU device (the dry-run's 512-device override
# lives ONLY in repro.launch.dryrun / subprocesses).
jax.config.update("jax_platform_name", "cpu")


def pytest_configure(config):
    # registered so the mark is known; nothing deselects on it
    config.addinivalue_line("markers", "slow: a test that takes minutes")


@pytest.fixture
def rng():
    return np.random.default_rng(0)
