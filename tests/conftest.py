"""Shared fixtures. NOTE: no XLA_FLAGS device-count override here — tests
run single-device by design; a test that needs several virtual devices
spawns its own process with them."""
import numpy as np
import pytest


def pytest_configure(config):
    # also declared in pyproject.toml; kept here so running pytest from a
    # different rootdir still knows the marker
    config.addinivalue_line(
        "markers", "slow: heavy convergence / end-to-end / compile tests")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
