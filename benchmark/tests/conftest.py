"""The benchmark's own tests: CPU rehearsals at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

# Tiny sizes of each configuration's generator, same shape parameters.
TINY = {"rmat": {"scale": 12}}


@pytest.fixture
def tiny_spec():
    """A cell's spec as the harness loads it, its graph cut to TINY."""
    from benchmark import harness

    def make(cell):
        spec = harness.load_spec(cell)
        gen = spec["config"]["generator"]
        gen.update(TINY[gen["kind"]])
        return spec

    return make
