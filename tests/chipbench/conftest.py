"""The benchmark's tests import ``chipbench`` from the checkout's root and
the small-size helpers beside this file."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (HERE, os.path.dirname(os.path.dirname(HERE))):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="session", autouse=True)
def no_persistent_compile_cache():
    """The runs here compile for the CPU; keep those programs out of the
    checkout's compilation cache, which the chip's runs use."""
    from chipbench import harness

    harness.enable_compile_cache = lambda: None


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    import chipbench_small

    return chipbench_small.make_root(str(tmp_path_factory.mktemp("bench")))
