"""Ahead-of-time compile of each cell's phase-0 loop for a described v5e.

The phase-0 loop (the driver's ``_run_phase_loop`` over the bucketed
step) is the program each clustering spends its device time in.  It is
built here from the cell's own generator at a size a test can hold
(``SIZES``, the same shape parameters), placed on a ``v5e:2x2``
topology that is described, not attached, and compiled.  Nothing runs.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library.
"""

import numpy as np
import pytest

from benchmark import generators, harness

SIZES = {"rmat": {"scale": 15}}


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("cell", ["rmat-s19.cluster"])
def test_phase0_loop_compiles_for_v5e(one_chip, cell):
    import jax

    from cuvite_tpu import Graph
    from cuvite_tpu.core.distgraph import DistGraph
    from cuvite_tpu.louvain import driver

    gen = dict(harness.load_spec(cell)["config"]["generator"])
    gen.update(SIZES[gen["kind"]])
    gd = generators.make_graph(gen, 12345)
    g = Graph(offsets=gd.offsets, tails=gd.tails, weights=gd.weights)
    dg = DistGraph.build(g, 1, min_nv_pad=4096, min_ne_pad=16384,
                         pad_edges=False)
    runner = driver.PhaseRunner(dg, engine="bucketed", release_slabs=True)

    def spec(x):
        if x is None:
            return None
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=one_chip)

    extra = jax.tree.map(spec, runner._extra)
    wdt = np.dtype(runner.constant.dtype)
    compiled = driver._run_phase_loop.lower(
        extra, spec(runner.comm0), np.asarray(1e-6, dtype=wdt),
        np.asarray(-1.0, dtype=wdt), call=runner._call,
        max_iters=driver.MAX_TOTAL_ITERATIONS).compile()
    assert "while" in compiled.as_text()
