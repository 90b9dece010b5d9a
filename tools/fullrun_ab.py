"""Full louvain_phases A/B (bench.py's timed body, minus the probe).

One warm-up + one timed run at AB_SCALE (default 18) on the backend pinned
by JAX_PLATFORMS.  Prints phase breakdown and TEPS for the timed run.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401

import jax

from cuvite_tpu.io.generate import generate_rmat
from cuvite_tpu.louvain.driver import louvain_phases


def teps(res):
    trav = sum(p.num_edges * p.iterations for p in res.phases)
    clus = sum(p.seconds for p in res.phases)
    return trav / max(clus, 1e-9), clus


def main():
    scale = int(os.environ.get("AB_SCALE", "18"))
    engine = os.environ.get("AB_ENGINE", "auto")
    print(f"# backend={jax.default_backend()} scale={scale} engine={engine}",
          flush=True)
    g = generate_rmat(scale, edge_factor=16, seed=1)
    t0 = time.perf_counter()
    res = louvain_phases(g, engine=engine)
    print(f"# warmup wall {time.perf_counter() - t0:.1f}s", flush=True)
    from cuvite_tpu.utils.trace import Tracer

    tr = Tracer()  # stage breakdown incl. coalesce_s (ISSUE 8)
    t0 = time.perf_counter()
    res = louvain_phases(g, engine=engine, verbose=False, tracer=tr)
    wall = time.perf_counter() - t0
    v, clus = teps(res)
    iters = sum(p.iterations for p in res.phases)
    print(f"Q={res.modularity:.5f} phases={len(res.phases)} iters={iters} "
          f"clustering={clus:.2f}s wall={wall:.1f}s "
          f"TEPS={v/1e6:.2f}M", flush=True)
    bd = tr.breakdown()
    stages = " ".join(f"{k}={bd[k]:.2f}" for k in sorted(bd))
    print(f"# stages: {stages}", flush=True)
    for p in res.phases:
        print(f"#   phase ne={p.num_edges} it={p.iterations} "
              f"t={p.seconds:.2f}s", flush=True)


if __name__ == "__main__":
    main()
