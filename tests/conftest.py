"""Test env: force an 8-device virtual CPU platform BEFORE jax initializes.

Multi-chip hardware is not available in CI; sharding tests run on a virtual
8-device CPU mesh (SURVEY.md §4 implication (d) — this replaces the
reference's Orleans-localhost multi-silo trick, ``TestApp/Program.cs:37-104``).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Tests are CPU-only by design: pin the platform at the config level
# before the first array op (a chip, where present, stays untouched).
from distributedratelimiting.redis_tpu.utils.cpu_bootstrap import (
    force_cpu_platform,
    set_virtual_device_count,
)

set_virtual_device_count(os.environ, 8)
# The suite never turns on the persistent compile cache, in this process
# or in the entry points its children run (utils/compile_cache.py).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
force_cpu_platform()
import jax

jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)
