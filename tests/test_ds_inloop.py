"""Scale-safe in-loop convergence (VERDICT r2 item 4): the device loop's
`(mod - prev_mod) < threshold` decision must run on double-single
accumulation above DS_MIN_TOTAL_WEIGHT, where plain f32 reductions lose
more than the threshold."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cuvite_tpu.ops import segment as seg
from cuvite_tpu.ops.exactsum import ds_psum, ds_tree_sum


def _adversarial_counter0(k: int = 64) -> np.ndarray:
    """[2^25, 1, 1, ... (k ones), 0-pad to 128]: XLA:CPU's f32 reduction of
    this array loses 16.0 absolute (measured, deterministic for the pinned
    jaxlib) while the f64 total is exact — the small-magnitude mass a big
    leading term absorbs, the miniature of the scale-28 failure mode."""
    a = np.zeros(128, dtype=np.float32)
    a[0] = 2.0 ** 25
    a[1:1 + k] = 1.0
    return a


def test_ds_modularity_terms_matches_f64_where_f32_loses():
    c0 = _adversarial_counter0()
    exact = float(np.sum(c0.astype(np.float64)))  # 2^25 + 64, f32-exact
    cd = np.zeros(4, dtype=np.float32)
    const = jnp.float32(1.0)

    def run(accum):
        f = jax.jit(lambda x, d: seg.modularity_terms(
            x, d, const, lambda v: v, accum))
        return float(f(jnp.asarray(c0), jnp.asarray(cd)))

    q32 = run("float32")
    qds = run(seg.DS_ACCUM)
    assert qds == exact, (qds, exact)
    # Canary: if XLA's f32 reduction ever becomes exact on this input, the
    # adversarial construction (and DS_MIN_TOTAL_WEIGHT) needs revisiting.
    assert q32 != exact, "f32 reduction unexpectedly exact; rebuild the test"


def test_threshold_decision_follows_ds():
    """The miniature of the scale-28 bug: with threshold between the f32 and
    ds modularity gains, the f32 loop stops a phase the ds loop continues —
    the driver must follow ds."""
    from cuvite_tpu.louvain.driver import _run_phase_loop

    c0 = jnp.asarray(_adversarial_counter0())
    cd = jnp.zeros(4, dtype=jnp.float32)
    const = jnp.float32(1.0)
    exact = float(np.sum(np.asarray(c0).astype(np.float64)))

    def make_call(accum):
        def call(comm, extra):
            mod = seg.modularity_terms(c0, cd, const, lambda v: v, accum)
            return comm, mod, jnp.int32(0), jnp.zeros((), bool)

        return call

    q32 = float(jax.jit(lambda: make_call("float32")(
        jnp.zeros(4, jnp.int32), ())[1])())
    assert q32 < exact
    # threshold strictly between the two gains over `lower`
    lower = np.float32(exact - 32.0)
    th = np.float32(exact - q32)  # ds gain = 32 >= th > f32 gain

    def iters(accum):
        _, _, it, _, _conv = _run_phase_loop(
            (), jnp.zeros(4, jnp.int32), th, lower,
            call=make_call(accum), max_iters=5)
        return int(it)

    assert iters("float32") == 1   # f32 sees no gain, stops immediately
    assert iters(seg.DS_ACCUM) == 2  # ds sees the real gain, iterates on


def test_ds_psum_exact_across_shards():
    """Cross-shard pair reduction must not re-lose the low words."""
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices("cpu")[:8]
    mesh = Mesh(np.array(devs), ("x",))
    # per-shard values whose hi parts alone would lose the +1s
    vals = np.tile(np.array([2.0 ** 25, 1.0], np.float32), 4)  # 8 shards

    @jax.jit
    @jax.shard_map(mesh=mesh, in_specs=P("x"), out_specs=P(),
               check_vma=False)
    def f(x):
        pair = ds_tree_sum(x)   # per-shard scalar pair
        hi, lo = ds_psum(pair, "x")
        return hi + lo, hi, lo

    tot, hi, lo = f(jnp.asarray(vals))
    exact = np.sum(vals.astype(np.float64))
    assert float(np.float64(hi) + np.float64(lo)) == float(exact)


@pytest.fixture(scope="module")
def weighted_karate():
    from tests.conftest import karate_edges

    from cuvite_tpu.core.graph import Graph

    nv, s, d = karate_edges()
    w = np.full(len(s), 2.0 ** 18, dtype=np.float64)
    return Graph.from_edges(nv, s, d, weights=w)


def test_runner_selects_ds_above_cutover(weighted_karate):
    from cuvite_tpu.core.distgraph import DistGraph
    from cuvite_tpu.louvain.driver import DS_MIN_TOTAL_WEIGHT, PhaseRunner

    assert weighted_karate.total_edge_weight_twice() >= DS_MIN_TOTAL_WEIGHT
    r = PhaseRunner(DistGraph.build(weighted_karate, 1), engine="bucketed")
    assert r.accum_name == seg.DS_ACCUM


def test_ds_driver_end_to_end(weighted_karate, karate):
    """Q is invariant under uniform weight scaling, so the ds-accum run on
    2^18-weighted karate must reproduce the unweighted golden value — on
    one shard, on a replicated mesh, and on the sparse exchange."""
    from cuvite_tpu.louvain.driver import louvain_phases

    q_ref = louvain_phases(karate).modularity
    for kw in ({}, {"nshards": 4, "exchange": "replicated"},
               {"nshards": 4, "exchange": "sparse"}):
        res = louvain_phases(weighted_karate, **kw)
        assert res.modularity == pytest.approx(q_ref, abs=2e-5), kw
