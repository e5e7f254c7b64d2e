import numpy as np
import pytest

# NOTE: no XLA_FLAGS here on purpose — tests run with the real (1) CPU
# device; only launch/dryrun.py forces 512 placeholder devices.


@pytest.fixture
def rng():
    return np.random.default_rng(0)
