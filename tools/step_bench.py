"""Single-step microbenchmark on the current default backend.

Times one bucketed sweep on a phase-0 R-MAT slab through the SAME
PhaseRunner the driver uses (no duplicated upload recipe), with a scalar
readback ending each timed window, and reports the dispatch round-trip
latency separately so device time can be read off the difference.

Usage:
    python tools/step_bench.py            # scale 18, default backend
    AB_SCALE=20 python tools/step_bench.py
    CUVITE_QUAD_MAX=256 python tools/step_bench.py   # dedup-cutover A/B
    JAX_PLATFORMS=cpu python tools/step_bench.py     # pin cpu backend
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401  (repo path + compile cache, must be first)

import jax

import jax.numpy as jnp
import numpy as np

from cuvite_tpu.core.distgraph import DistGraph
from cuvite_tpu.io.generate import generate_rmat
from cuvite_tpu.louvain.bucketed import QUADRATIC_MAX_WIDTH
from cuvite_tpu.louvain.driver import PhaseRunner


def main():
    scale = int(os.environ.get("AB_SCALE", "18"))
    print(f"# backend={jax.default_backend()} scale={scale} "
          f"QUAD_MAX={QUADRATIC_MAX_WIDTH}", flush=True)
    g = generate_rmat(scale, edge_factor=16, seed=1)
    t0 = time.perf_counter()
    dg = DistGraph.build(g, 1)
    runner = PhaseRunner(dg, engine="bucketed")
    # Force upload completion with a real readback (not block_until_ready).
    _ = np.asarray(runner.comm0[0:1])
    print(f"# plan+upload {time.perf_counter() - t0:.2f}s", flush=True)

    comm = runner.comm0

    def step(c):
        return runner._step(None, None, None, c, runner.vdeg,
                            runner.constant)

    t0 = time.perf_counter()
    out = step(comm)
    _ = float(out[1])
    print(f"# first call (compile) {time.perf_counter() - t0:.1f}s",
          flush=True)

    # Dispatch round-trip latency baseline: warm the exact timed
    # expression first, then take min-of-5 like the step timing.
    x = jnp.zeros(())
    _ = float(jnp.add(x, 1.0))
    rtts = []
    for _ in range(5):
        t0 = time.perf_counter()
        _ = float(jnp.add(x, 1.0))
        rtts.append(time.perf_counter() - t0)
    rtt = min(rtts)
    print(f"# scalar round-trip {rtt*1e3:.1f} ms", flush=True)

    c = comm
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        tgt, mod, _, _ = step(c)
        _ = float(mod)
        times.append(time.perf_counter() - t0)
        c = tgt
    best = min(times)
    print(f"step+fetch {best*1e3:.1f} ms  (~device {max(best-rtt,0)*1e3:.1f} "
          f"ms, {g.num_edges/max(best-rtt,1e-9)/1e6:.1f} M edges/s)")


if __name__ == "__main__":
    main()
