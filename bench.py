"""Benchmark entry point: multi-phase Louvain TEPS on one chip.

The harness logic lives in cuvite_tpu.workloads.bench (warm-up,
compile-count==0 guard on the first timed run, best-of-N, budget
handling, shared JSON schema); this shim keeps the historical
`python bench.py` invocation and BENCH_* env knobs working.  It runs on
JAX's default backend, and the record names the device.  Prints ONE JSON
line on success; exits 3 WITHOUT a JSON when the compile guard trips.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# Persistent XLA compilation cache (opt out with CUVITE_NO_COMPILE_CACHE=1).
from cuvite_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()

from cuvite_tpu.workloads.bench import main

if __name__ == "__main__":
    sys.exit(main())
