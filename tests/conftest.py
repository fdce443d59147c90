"""Shared fixtures and the hypothesis profile."""
import numpy as np
import pytest
from hypothesis import HealthCheck, settings

# CPU in this container is slow and single-core; disable deadlines globally.
settings.register_profile(
    "repro", deadline=None, max_examples=25,
    suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large])
settings.load_profile("repro")


@pytest.fixture
def rng():
    return np.random.default_rng(0)
