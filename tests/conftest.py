"""Shared test fixtures. NOTE: no XLA_FLAGS here — the test process must see
the single real CPU device; tests that need more shards force host devices
in a subprocess of their own."""

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)
