import os

import numpy as np
import pytest

try:  # hypothesis is a dev-only extra; property tests auto-skip without it
    from hypothesis import settings
except ModuleNotFoundError:
    settings = None
else:
    # keep hypothesis fast on the single-core container
    settings.register_profile("ci", max_examples=15, deadline=None)
    settings.load_profile("ci")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def pytest_configure(config):
    """Under pytest-xdist, share the cores among the workers' BLAS pools.

    Each worker's OpenBLAS would otherwise start one thread per core, and
    six such pools spinning on the same cores make a 0.2 s host LAPACK
    call take seconds. The controller runs this hook before it starts
    the workers, which inherit the variable; numpy in this process has
    already loaded its BLAS and is unaffected."""
    n = getattr(config.option, "numprocesses", None)
    if isinstance(n, int) and n > 1:
        os.environ.setdefault("OPENBLAS_NUM_THREADS",
                              str(max(1, (os.cpu_count() or 1) // n)))
