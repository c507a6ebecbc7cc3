import os
import sys

# tests and benches must see ONE device — do not set device-count flags here.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
