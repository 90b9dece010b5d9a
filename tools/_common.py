"""Shared bootstrap for the tools/ scripts.

Importing this module (FIRST, before anything touches a jax backend):
- puts the repo root on sys.path;
- points jax at the repo's persistent compile cache.

The backend is JAX's default; pin one with JAX_PLATFORMS (e.g. cpu).
"""

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from cuvite_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
