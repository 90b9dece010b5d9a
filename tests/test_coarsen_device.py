"""Device-resident inter-phase coarsening (cuvite_tpu/coarsen/device.py).

``coarsen/rebuild.py`` is the bit-parity oracle: the device renumber must
reproduce np.unique's sorted-order dense ids (rebuild.cpp:167-197), and
the device relabel+coalesce must reproduce the host CSR coalesce
(offsets, tails, weights) bit-for-bit wherever the run sums are exactly
representable — unit and dyadic weights here, which is the documented
exactness domain (the host accumulates f64 and casts once; the device
accumulates in the weight dtype, or ds32 pairs in the scale-safe mode).

The transfer/compile guards pin the tentpole property: a phase
transition within the same pow2 slab class performs zero host transfers
of O(E) arrays and zero fresh XLA compiles from phase 2 on.
"""

import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cuvite_tpu.coarsen.device import (
    device_coarsen_slab,
    device_renumber,
    shrink_slab,
)
from cuvite_tpu.coarsen.rebuild import coarsen_graph, renumber_communities
from cuvite_tpu.core.distgraph import DistGraph
from cuvite_tpu.core.graph import Graph
from cuvite_tpu.core.types import Policy, wide_policy
from cuvite_tpu.io.generate import generate_rmat
from cuvite_tpu.louvain.driver import louvain_phases
from cuvite_tpu.utils.trace import Tracer


@pytest.fixture(scope="module")
def rmat10():
    g = generate_rmat(10, edge_factor=8, seed=3)
    # Precondition for the class-stability tests below: the whole run fits
    # the floor class (nv_pad 4096 / ne_pad 16384), so EVERY phase shares
    # one compiled-step cache entry.
    assert g.num_vertices <= 4096 and g.num_edges <= 16384
    return g


def _device_coarse(graph, labels_pad, accum=None):
    """Run the device pipeline on graph's single-shard slab and return the
    coarse CSR (offsets, tails, weights), nc, and the dense map."""
    dg = DistGraph.build(graph, 1)
    sh = dg.shards[0]
    src = jnp.asarray(np.asarray(sh.src))
    dst = jnp.asarray(np.asarray(sh.dst))
    w = jnp.asarray(np.asarray(sh.w))
    comm = jnp.asarray(np.asarray(labels_pad).astype(np.asarray(src).dtype))
    mask = jnp.asarray(dg.vertex_mask())
    out = device_coarsen_slab(src, dst, w, comm, mask, nv_pad=dg.nv_pad,
                              accum_dtype=accum)
    src2, dst2, w2, dmap, nc, ne2 = jax.device_get(out)
    nc, ne2 = int(nc), int(ne2)
    offsets = np.zeros(nc + 1, dtype=np.int64)
    np.cumsum(np.bincount(src2[:ne2], minlength=nc), out=offsets[1:])
    # Padding contract: everything past ne2 is sentinel/zero.
    assert (src2[ne2:] == dg.nv_pad).all()
    assert (w2[ne2:] == 0).all()
    return offsets, dst2[:ne2], w2[:ne2], nc, dmap, dg


def _host_coarse(graph, labels_pad):
    dg = DistGraph.build(graph, 1)
    comm_old = np.asarray(labels_pad)[dg.old_to_pad]
    dense, nc = renumber_communities(comm_old)
    gh = coarsen_graph(graph, dense, nc)
    return gh, dense, nc


def _random_padded_labels(graph, nv_pad, rng, gapped=False):
    """A labeling in padded space: every real vertex points at some real
    vertex id.  ``gapped``: only a sparse subset of ids survive, leaving
    large gaps in the label space (the renumber's hard case)."""
    nv = graph.num_vertices
    if gapped:
        pool = rng.choice(nv, size=max(nv // 13, 2), replace=False)
    else:
        pool = np.arange(nv)
    lab = np.full(nv_pad, nv_pad - 1, dtype=np.int64)
    lab[:nv] = rng.choice(pool, size=nv)
    return lab


@pytest.mark.parametrize("gapped", [False, True],
                         ids=["dense-ish", "gapped-labels"])
@pytest.mark.parametrize("accum", [None, "ds32"])
def test_device_matches_host_bitwise_unit_weights(rmat10, gapped, accum):
    dg = DistGraph.build(rmat10, 1)
    rng = np.random.default_rng(7)
    lab = _random_padded_labels(rmat10, dg.nv_pad, rng, gapped=gapped)
    off_d, tails_d, w_d, nc_d, dmap, _ = _device_coarse(
        rmat10, lab, accum=accum)
    gh, dense, nc_h = _host_coarse(rmat10, lab)
    assert nc_d == nc_h
    assert np.array_equal(off_d, gh.offsets)
    assert np.array_equal(tails_d, gh.tails)
    # Unit weights: every run sum is an exact small integer in f32 — the
    # host's f64-accumulate-then-cast is bit-identical.
    assert np.array_equal(w_d, gh.weights)
    # The device dense map agrees with np.unique's sorted-order ids.
    comm_old = lab[dg.old_to_pad]
    assert np.array_equal(np.asarray(dmap)[comm_old], dense)


@pytest.mark.parametrize("accum", [None, "ds32"])
def test_batched_coarsen_matches_per_graph_host(accum):
    """The serving path's coarsen: batched_coarsen_slab over B graphs of
    one slab class equals, row by row, the host coarsen_graph of each
    graph under its own labelling."""
    from cuvite_tpu.coarsen.device import (
        batched_coarsen_slab,
        batched_renumber,
    )

    rng = np.random.default_rng(17)
    graphs = [generate_rmat(9, edge_factor=8, seed=s) for s in (1, 2, 3)]
    dgs = [DistGraph.build(g, 1) for g in graphs]
    nv_pad, ne_pad = dgs[0].nv_pad, dgs[0].ne_pad
    assert all((d.nv_pad, d.ne_pad) == (nv_pad, ne_pad) for d in dgs)
    labs = [_random_padded_labels(g, nv_pad, rng, gapped=(i == 1))
            for i, g in enumerate(graphs)]

    def stack(f):
        return jnp.asarray(np.stack([f(d, lab) for d, lab in zip(dgs, labs)]))

    src = stack(lambda d, _l: np.asarray(d.shards[0].src))
    dst = stack(lambda d, _l: np.asarray(d.shards[0].dst))
    w = stack(lambda d, _l: np.asarray(d.shards[0].w))
    comm = stack(lambda d, lab: lab.astype(np.int32))
    mask = stack(lambda d, _l: d.vertex_mask())
    dmap, nc = batched_renumber(comm, mask, nv_pad=nv_pad)
    src2, dst2, w2, _dm, nc2, ne2 = jax.device_get(batched_coarsen_slab(
        src, dst, w, comm, mask, dmap, nc, nv_pad=nv_pad,
        accum_dtype=accum))
    for b, (g, lab) in enumerate(zip(graphs, labs)):
        gh, _dense, nc_h = _host_coarse(g, lab)
        n = int(ne2[b])
        assert int(nc2[b]) == nc_h and n == gh.num_edges
        assert np.array_equal(src2[b, :n], gh.sources())
        assert np.array_equal(dst2[b, :n], gh.tails)
        assert np.array_equal(w2[b, :n], gh.weights)
        assert (src2[b, n:] == nv_pad).all() and (w2[b, n:] == 0).all()


def test_device_renumber_matches_np_unique_on_gaps(rmat10):
    dg = DistGraph.build(rmat10, 1)
    rng = np.random.default_rng(11)
    lab = _random_padded_labels(rmat10, dg.nv_pad, rng, gapped=True)
    dmap, nc = jax.device_get(device_renumber(
        jnp.asarray(lab.astype(np.int32)), jnp.asarray(dg.vertex_mask()),
        nv_pad=dg.nv_pad))
    dense, nc_h = renumber_communities(lab[dg.old_to_pad])
    assert int(nc) == nc_h
    assert np.array_equal(dmap[lab[dg.old_to_pad]], dense)


def test_self_loop_accumulation_collapses_cliques(two_cliques):
    """Both K5 cliques collapse to single vertices: ALL intra-community
    weight must land on the diagonal (rebuild.cpp:244-279), and the
    bridge edge survives off-diagonal — compared bit-wise vs the host."""
    dg = DistGraph.build(two_cliques, 1)
    lab = np.arange(dg.nv_pad, dtype=np.int64)
    lab[:5] = 0
    lab[5:10] = 5
    off_d, tails_d, w_d, nc_d, _, _ = _device_coarse(two_cliques, lab)
    gh, _, nc_h = _host_coarse(two_cliques, lab)
    assert nc_d == nc_h == 2
    assert np.array_equal(off_d, gh.offsets)
    assert np.array_equal(tails_d, gh.tails)
    assert np.array_equal(w_d, gh.weights)
    # Diagonal of community 0 = both directions of the 10 K5 edges.
    sl = w_d[(np.repeat(np.arange(2), np.diff(off_d)) == 0) & (tails_d == 0)]
    assert sl.sum() == 20.0


@pytest.mark.parametrize("accum", [None, "ds32"])
def test_dyadic_f32_weights_bitwise(accum):
    """Non-unit weights: dyadic values (multiples of 1/8) keep every run
    sum exact in f32, so device == host remains BIT equality, in both
    accumulation modes."""
    rng = np.random.default_rng(3)
    nv = 96
    src = rng.integers(0, nv, 600)
    dst = rng.integers(0, nv, 600)
    w = rng.integers(1, 64, 600).astype(np.float64) / 8.0
    g = Graph.from_edges(nv, src, dst, weights=w)
    dgp = DistGraph.build(g, 1)
    lab = _random_padded_labels(g, dgp.nv_pad, rng)
    off_d, tails_d, w_d, nc_d, _, _ = _device_coarse(g, lab, accum=accum)
    gh, _, nc_h = _host_coarse(g, lab)
    assert nc_d == nc_h
    assert np.array_equal(off_d, gh.offsets)
    assert np.array_equal(tails_d, gh.tails)
    assert np.array_equal(w_d, gh.weights)


def test_wide_policy_weights_match_after_device_cast():
    """bits64 graphs: the device clamps to f32/int32 (no x64 here), so the
    host f64 oracle is compared after one lossless cast (dyadic weights,
    bounded sums) — value equality at the device dtype."""
    rng = np.random.default_rng(5)
    nv = 64
    src = rng.integers(0, nv, 400)
    dst = rng.integers(0, nv, 400)
    w = rng.integers(1, 16, 400).astype(np.float64) / 4.0
    g = Graph.from_edges(nv, src, dst, weights=w, policy=wide_policy())
    assert g.weights.dtype == np.float64
    dgp = DistGraph.build(g, 1)
    lab = _random_padded_labels(g, dgp.nv_pad, rng, gapped=True)
    off_d, tails_d, w_d, nc_d, _, _ = _device_coarse(g, lab)
    gh, _, nc_h = _host_coarse(g, lab)
    assert nc_d == nc_h
    assert np.array_equal(off_d, gh.offsets)
    assert np.array_equal(tails_d, np.asarray(gh.tails).astype(tails_d.dtype))
    assert np.array_equal(w_d, np.asarray(gh.weights).astype(np.float32))


def test_shrink_slab_prefix_and_sentinel():
    src = jnp.asarray(np.array([0, 1, 2, 64, 64, 64, 64, 64], np.int32))
    dst = jnp.asarray(np.array([1, 2, 0, 0, 0, 0, 0, 0], np.int32))
    w = jnp.asarray(np.ones(8, np.float32))
    s, d, ww = shrink_slab(src, dst, w, new_nv_pad=4, new_ne_pad=4)
    assert s.shape == d.shape == ww.shape == (4,)
    # Real ids survive; old sentinels (64) rewrite to the new class's.
    assert np.array_equal(np.asarray(s), [0, 1, 2, 4])


# ---------------------------------------------------------------------------
# End-to-end: device transition == host transition, and the guards


@pytest.fixture(scope="module")
def dyadic_graph():
    """A weighted graph in the exactness domain: 1/8-multiple weights
    and self-loops, so device and host coalesces agree bit for bit."""
    rng = np.random.default_rng(21)
    nv, ne = 1500, 6000
    src = rng.integers(0, nv, ne)
    dst = np.where(rng.random(ne) < 0.05, src, rng.integers(0, nv, ne))
    w = rng.integers(1, 32, ne).astype(np.float64) / 8.0
    return Graph.from_edges(nv, src, dst, weights=w)


@pytest.mark.parametrize("graph", ["rmat10", "dyadic_graph"])
def test_sort_engine_device_vs_host_full_run(graph, monkeypatch, request):
    g = request.getfixturevalue(graph)
    monkeypatch.setenv("CUVITE_DEVICE_COARSEN", "0")
    r0 = louvain_phases(g, engine="sort")
    monkeypatch.delenv("CUVITE_DEVICE_COARSEN")
    r1 = louvain_phases(g, engine="sort")
    assert len(r0.phases) == len(r1.phases) >= 3
    assert r0.total_iterations == r1.total_iterations
    assert r0.modularity == r1.modularity  # both use the device ds pass
    assert np.array_equal(r0.communities, r1.communities)


def test_fused_device_vs_host_full_run(rmat10, monkeypatch):
    import cuvite_tpu.louvain.driver as drv

    # Force the multilevel (one-call-per-phase) path on this small graph.
    monkeypatch.setattr(drv, "FUSED_SHRINK_EDGES", 1 << 10)
    monkeypatch.setenv("CUVITE_DEVICE_COARSEN", "0")
    r0 = louvain_phases(rmat10, engine="fused", threshold_cycling=True)
    monkeypatch.delenv("CUVITE_DEVICE_COARSEN")
    r1 = louvain_phases(rmat10, engine="fused", threshold_cycling=True)
    assert len(r0.phases) == len(r1.phases) >= 3
    assert r0.total_iterations == r1.total_iterations
    assert np.array_equal(r0.communities, r1.communities)
    # Final Q: device ds pass vs host f64 oracle — f64-class agreement.
    assert r1.modularity == pytest.approx(r0.modularity, abs=1e-12)


def _no_big_fetch_guard(monkeypatch, cap):
    """Reject any device->host fetch above ``cap`` elements: O(V)=nv_pad
    stays legal, an O(E)=ne_pad slab pull trips.  BOTH spellings are
    guarded — ``jax.device_get(x)`` and the ``np.asarray(x)`` route
    (jax.Array.__array__ does not go through device_get), so a regression
    that re-materializes the slab via numpy is caught too."""
    orig = jax.device_get

    def guarded(x):
        for leaf in jax.tree_util.tree_leaves(x):
            size = int(getattr(leaf, "size", 0) or 0)
            assert size <= cap, \
                f"O(E)-sized device->host fetch ({size} > {cap} elements)"
        return orig(x)

    monkeypatch.setattr(jax, "device_get", guarded)
    from jax._src import array as _jarray

    orig_arr = _jarray.ArrayImpl.__array__

    def guarded_arr(self, *a, **k):
        assert int(self.size) <= cap, \
            f"O(E)-sized np.asarray of a device array ({int(self.size)} " \
            f"> {cap} elements)"
        return orig_arr(self, *a, **k)

    monkeypatch.setattr(_jarray.ArrayImpl, "__array__", guarded_arr)


def test_sort_engine_transition_zero_host_rebuild(rmat10, monkeypatch):
    """The tentpole transfer guard: across a multi-phase sort-engine run,
    the host builds the DistGraph ONCE (phase 0), never runs the host
    coarsener, and never fetches an O(E) array from the device."""
    import cuvite_tpu.louvain.driver as drv

    builds = []
    orig_build = DistGraph.build

    def counting_build(*a, **k):
        builds.append(1)
        return orig_build(*a, **k)

    monkeypatch.setattr(DistGraph, "build", staticmethod(counting_build))

    def boom(*a, **k):
        raise AssertionError("host coarsen_graph on the device path")

    monkeypatch.setattr(drv, "coarsen_graph", boom)
    _no_big_fetch_guard(monkeypatch, cap=4096)  # nv_pad; ne_pad is 16384
    res = louvain_phases(rmat10, engine="sort")
    assert len(builds) == 1
    assert len(res.phases) >= 3
    assert res.modularity > 0


def test_fused_transition_zero_host_rebuild(rmat10, monkeypatch):
    import cuvite_tpu.louvain.driver as drv

    monkeypatch.setattr(drv, "FUSED_SHRINK_EDGES", 1 << 10)
    builds = []
    orig_build = DistGraph.build

    def counting_build(*a, **k):
        builds.append(1)
        return orig_build(*a, **k)

    monkeypatch.setattr(DistGraph, "build", staticmethod(counting_build))

    def boom(*a, **k):
        raise AssertionError("host coarsen_graph on the device path")

    monkeypatch.setattr(drv, "coarsen_graph", boom)
    _no_big_fetch_guard(monkeypatch, cap=4096)
    res = louvain_phases(rmat10, engine="fused")
    assert len(builds) == 1
    assert len(res.phases) >= 3
    assert res.modularity > 0


class _PhaseCompileProbe(Tracer):
    """Tracer that snapshots the compile-log length at every iterate-stage
    ENTRY, so the test can pin which phase triggered which compiles."""

    def __init__(self, compile_log):
        super().__init__(enabled=True)
        self._log = compile_log
        self.marks = []

    @contextlib.contextmanager
    def stage(self, name):
        if name == "iterate":
            self.marks.append(len(self._log))
        with super().stage(name):
            yield


@pytest.mark.parametrize("engine", ["sort", "fused"])
def test_three_phase_run_zero_fresh_compiles_after_phase1(
        rmat10, engine, monkeypatch):
    """Same pow2 class across every phase (floors 4096/16384) => the
    compiled-step cache must serve phases 2+ entirely: all XLA compiles
    happen in phases 0-1 (step + coarsen pipelines), none after."""
    import logging

    import cuvite_tpu.louvain.driver as drv

    if engine == "fused":
        # Force the one-call-per-phase multilevel path (the small-graph
        # default runs everything in ONE call — nothing to probe).
        monkeypatch.setattr(drv, "FUSED_SHRINK_EDGES", 1 << 10)
    compiles = []

    class _Grab(logging.Handler):
        def emit(self, record):
            if "Compiling" in record.getMessage():
                compiles.append(record.getMessage())

    probe = _PhaseCompileProbe(compiles)
    handler = _Grab(level=logging.WARNING)
    logger = logging.getLogger("jax")
    logger.addHandler(handler)
    jax.config.update("jax_log_compiles", True)
    try:
        res = louvain_phases(rmat10, engine=engine, tracer=probe)
    finally:
        jax.config.update("jax_log_compiles", False)
        logger.removeHandler(handler)
    n_calls = len(probe.marks)
    assert len(res.phases) >= 3 and n_calls >= 3
    fresh_after_phase1 = len(compiles) - probe.marks[2]
    assert fresh_after_phase1 == 0, (
        f"phase 2+ recompiled {fresh_after_phase1}x in the same slab "
        f"class: {compiles[probe.marks[2]:][:4]}")


def test_from_device_slab_metadata(rmat10):
    dg = DistGraph.build(rmat10, 1)
    sh = dg.shards[0]
    src = jnp.asarray(np.asarray(sh.src))
    dst = jnp.asarray(np.asarray(sh.dst))
    w = jnp.asarray(np.asarray(sh.w))
    ddg = DistGraph.from_device_slab(
        src, dst, w, num_vertices=rmat10.num_vertices,
        num_edges=rmat10.num_edges, nv_pad=dg.nv_pad, ne_pad=dg.ne_pad,
        policy=Policy(), total_weight_twice=rmat10.total_edge_weight_twice())
    assert ddg.device_resident and ddg.nshards == 1
    assert ddg.graph.num_vertices == rmat10.num_vertices
    assert ddg.graph.total_edge_weight_twice() \
        == rmat10.total_edge_weight_twice()
    # stacked_edges hands the jax arrays back without a host round-trip.
    s2, d2, w2 = ddg.stacked_edges()
    assert s2 is src and d2 is dst and w2 is w
    # padded degrees come from a device segment sum and match the host's.
    vdeg_dev = np.asarray(ddg.padded_weighted_degrees())
    vdeg_host = dg.padded_weighted_degrees()
    assert np.array_equal(vdeg_dev, vdeg_host)
    assert np.array_equal(ddg.vertex_mask(), dg.vertex_mask())
