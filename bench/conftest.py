"""Test set-up for the benchmark's own tests: the benchmark's modules
and the program's package import as they do under ``bench/run.py``,
and x64 stays off as it does there."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.join(os.path.dirname(BENCH), "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True)
def _no_x64():
    import jax
    with jax.enable_x64(False):
        yield
