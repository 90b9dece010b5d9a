"""Native host-runtime parity tests.

Every native entry point (native/cuvite_native.cpp via cuvite_tpu.native)
must be bit-identical to its pure-numpy fallback — the library is an
accelerator, not a semantic variant.  Skipped wholesale when the library
cannot be built/loaded (e.g. no compiler in the deployment image).
"""

import os

import numpy as np
import pytest

from cuvite_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable"
)


def _random_edges(ne, nv, seed, self_loops=True, dups=True):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, nv, size=ne)
    dst = rng.integers(0, nv, size=ne)
    if not self_loops:
        dst = np.where(src == dst, (dst + 1) % nv, dst)
    if dups:
        src[: ne // 4] = src[ne // 2 : ne // 2 + ne // 4]
        dst[: ne // 4] = dst[ne // 2 : ne // 2 + ne // 4]
    w = rng.random(ne)
    return src, dst, w


@pytest.mark.parametrize("symmetrize", [True, False])
@pytest.mark.parametrize("seed", [0, 7])
def test_build_csr_matches_numpy(symmetrize, seed):
    from cuvite_tpu.core.graph import Graph

    nv, ne = 257, 4096
    src, dst, w = _random_edges(ne, nv, seed)
    off_n, tails_n, w_n = native.build_csr(nv, src, dst, w, symmetrize)
    # Force the numpy path (edge count below the native threshold).
    g = Graph.from_edges(nv, src, dst, weights=w, symmetrize=symmetrize)
    assert np.array_equal(off_n, g.offsets)
    assert np.array_equal(tails_n, g.tails)
    # Weight sums accumulate duplicates in the same (input) order on both
    # paths, so equality after the policy-dtype cast is exact, not
    # approximate (native returns the raw f64 sums).
    assert np.array_equal(w_n.astype(g.weights.dtype), g.weights)


@pytest.mark.parametrize("symmetrize", [True, False])
def test_build_csr_radix_branch_matches_numpy(symmetrize):
    """nv > 2^22 forces the LSD-radix branch (the small-nv dense-accumulator
    fast path covers every other CSR test): its bit-identical-to-numpy
    contract for production-scale graphs must stay pinned.  Edges are
    concentrated on high vertex ids so the sparse offsets array stays
    cheap."""
    from cuvite_tpu.core.graph import Graph

    nv = (1 << 22) + 11
    ne = 4096
    rng = np.random.default_rng(3)
    src = rng.integers(nv - 300, nv, size=ne)
    dst = rng.integers(nv - 300, nv, size=ne)
    src[: ne // 4] = src[ne // 2: ne // 2 + ne // 4]   # duplicates
    dst[: ne // 4] = dst[ne // 2: ne // 2 + ne // 4]
    w = rng.random(ne)
    off_n, tails_n, w_n = native.build_csr(nv, src, dst, w, symmetrize)
    g = Graph.from_edges(nv, src, dst, weights=w, symmetrize=symmetrize)
    assert np.array_equal(off_n, g.offsets)
    assert np.array_equal(tails_n, g.tails)
    assert np.array_equal(w_n.astype(g.weights.dtype), g.weights)


def test_build_csr_rejects_out_of_range():
    with pytest.raises(ValueError):
        native.build_csr(4, np.array([0, 5]), np.array([1, 2]),
                         np.ones(2), True)


def test_from_edges_uses_native_above_threshold():
    """Above the 2^16-edge threshold Graph.from_edges routes through the
    native builder; result must equal the numpy path bit-for-bit."""
    from cuvite_tpu.core.graph import Graph

    nv, ne = 1000, (1 << 16) + 11
    src, dst, w = _random_edges(ne, nv, 3)
    g_native = Graph.from_edges(nv, src, dst, weights=w)
    os.environ["CUVITE_NO_NATIVE"] = "1"
    native._LIB = None
    try:
        g_numpy = Graph.from_edges(nv, src, dst, weights=w)
    finally:
        del os.environ["CUVITE_NO_NATIVE"]
        native._LIB = None
    assert np.array_equal(g_native.offsets, g_numpy.offsets)
    assert np.array_equal(g_native.tails, g_numpy.tails)
    assert np.array_equal(g_native.weights, g_numpy.weights)


@pytest.mark.parametrize("scale,ne", [(8, 1 << 11), (12, 3000)])
def test_rmat_matches_numpy(scale, ne):
    from cuvite_tpu.io.generate import rmat_edges_numpy

    s_n, d_n = native.rmat_edges(scale, ne, 1, 0.57, 0.19, 0.19)
    s_p, d_p = rmat_edges_numpy(scale, ne, 1, 0.57, 0.19, 0.19)
    assert np.array_equal(s_n, s_p)
    assert np.array_equal(d_n, d_p)
    assert s_n.min() >= 0 and s_n.max() < (1 << scale)


def test_rmat_is_skewed():
    """R-MAT must produce a heavy-tailed degree distribution (sanity that
    the quadrant recursion actually biases, not uniform noise)."""
    s, d = native.rmat_edges(12, 1 << 14, 1, 0.57, 0.19, 0.19)
    deg = np.bincount(np.concatenate([s, d]), minlength=1 << 12)
    assert deg.max() > 8 * max(deg.mean(), 1)


@pytest.mark.parametrize("bits64", [True, False])
def test_vite_native_roundtrip(tmp_path, bits64):
    from cuvite_tpu.core.graph import Graph
    from cuvite_tpu.core.types import default_policy, wide_policy
    from cuvite_tpu.io.vite import read_vite, write_vite

    nv, ne = 300, 70000  # above the native read/write threshold
    src, dst, w = _random_edges(ne, nv, 5)
    w = np.round(w * 16) / 16  # exact in float32 for the 32-bit format
    policy = wide_policy() if bits64 else default_policy()
    g = Graph.from_edges(nv, src, dst, weights=w, policy=policy)
    p = str(tmp_path / "g.bin")
    write_vite(p, g, bits64=bits64)  # native write
    g2 = read_vite(p, bits64=bits64)  # native read
    os.environ["CUVITE_NO_NATIVE"] = "1"
    native._LIB = None
    try:
        g3 = read_vite(p, bits64=bits64)  # numpy memmap read
    finally:
        del os.environ["CUVITE_NO_NATIVE"]
        native._LIB = None
    for a, b in ((g2, g) , (g3, g)):
        assert np.array_equal(a.offsets, b.offsets)
        assert np.array_equal(a.tails, b.tails)
        assert np.array_equal(a.weights, b.weights)


def test_vite_native_vertex_range(tmp_path):
    from cuvite_tpu.core.graph import Graph
    from cuvite_tpu.io.vite import read_vite, write_vite

    nv, ne = 128, 70000
    src, dst, w = _random_edges(ne, nv, 9)
    g = Graph.from_edges(nv, src, dst, weights=w)
    p = str(tmp_path / "g.bin")
    write_vite(p, g)
    lo, hi = 32, 96
    part = read_vite(p, vertex_range=(lo, hi))
    assert part.num_vertices == hi - lo
    e0, e1 = int(g.offsets[lo]), int(g.offsets[hi])
    assert np.array_equal(part.offsets, g.offsets[lo : hi + 1] - e0)
    assert np.array_equal(part.tails, g.tails[e0:e1])


def test_balanced_parts_matches_python():
    from cuvite_tpu.core.distgraph import balanced_parts
    from cuvite_tpu.core.graph import Graph

    nv, ne = 500, 120000
    src, dst, w = _random_edges(ne, nv, 11)
    g = Graph.from_edges(nv, src, dst, weights=w)
    for nparts in (2, 4, 7):
        p_py = balanced_parts(g, nparts)
        p_nat = native.balanced_parts(g.offsets, nparts)
        assert np.array_equal(p_py, p_nat)


def test_balanced_parts_tiny_graph_matches_python():
    """ne < nparts drives some edge targets to 0; both paths must agree on
    the degenerate cuts (shard 0 never empty)."""
    from cuvite_tpu.core.distgraph import balanced_parts
    from cuvite_tpu.core.graph import Graph

    g = Graph.from_edges(10, np.array([0, 3]), np.array([1, 4]))
    for nparts in (3, 8):
        assert np.array_equal(balanced_parts(g, nparts),
                              native.balanced_parts(g.offsets, nparts))


def test_coarsen_native_matches_numpy():
    """coarsen_graph must be bit-identical with and without the native
    library (same duplicate-accumulation order), including f64 weights."""
    from cuvite_tpu.coarsen.rebuild import coarsen_graph, renumber_communities
    from cuvite_tpu.core.graph import Graph
    from cuvite_tpu.core.types import wide_policy

    nv, ne = 400, 40000  # slab 2*ne > 2^16 -> native path eligible
    src, dst, w = _random_edges(ne, nv, 13)
    g = Graph.from_edges(nv, src, dst, weights=w, policy=wide_policy())
    comm = (np.arange(nv) * 7919) % 37
    dense, nc = renumber_communities(comm)
    cg_native = coarsen_graph(g, dense, nc)
    os.environ["CUVITE_NO_NATIVE"] = "1"
    native._LIB = None
    try:
        cg_numpy = coarsen_graph(g, dense, nc)
    finally:
        del os.environ["CUVITE_NO_NATIVE"]
        native._LIB = None
    assert np.array_equal(cg_native.offsets, cg_numpy.offsets)
    assert np.array_equal(cg_native.tails, cg_numpy.tails)
    assert np.array_equal(cg_native.weights, cg_numpy.weights)


# ---------------------------------------------------------------------------
# Native bucket-plan builder (cv_plan_scan + cv_bucket_fill): bit-identical
# to the numpy BucketPlan.build, including the heavy class, weighted
# graphs, and the uint8 unit-weight compression.

def _numpy_plan(src, dst, w, nv_local, base):
    from cuvite_tpu.louvain.bucketed import BucketPlan

    old = native._LIB
    native._LIB = False  # force the numpy path
    try:
        return BucketPlan.build(src, dst, w, nv_local=nv_local, base=base)
    finally:
        native._LIB = old


def _assert_plans_equal(pn, pp):
    assert len(pn.buckets) == len(pp.buckets)
    for a, b in zip(pn.buckets, pp.buckets):
        assert a.width == b.width
        assert np.array_equal(a.verts, b.verts)
        assert np.array_equal(a.dst, b.dst)
        assert a.w.dtype == b.w.dtype
        assert np.array_equal(a.w, b.w)
    for f in ("heavy_src", "heavy_dst", "heavy_w", "self_loop"):
        assert np.array_equal(getattr(pn, f), getattr(pp, f)), f
    assert pn.has_heavy == pp.has_heavy


def _slab(g, nsh=1, s=0):
    from cuvite_tpu.core.distgraph import DistGraph

    dg = DistGraph.build(g, nsh)
    sh = dg.shards[s]
    return (np.asarray(sh.src), np.asarray(sh.dst), np.asarray(sh.w),
            dg.nv_pad, s * dg.nv_pad)


def test_bucket_plan_native_matches_numpy_rmat():
    from cuvite_tpu.io.generate import generate_rmat
    from cuvite_tpu.louvain.bucketed import _build_native

    src, dst, w, nvp, base = _slab(generate_rmat(14, edge_factor=16, seed=1))
    pn = _build_native(src, dst, w, nvp, base,
                       widths=__import__("cuvite_tpu.louvain.bucketed",
                                         fromlist=["DEFAULT_BUCKETS"]
                                         ).DEFAULT_BUCKETS)
    assert pn is not None
    # (R-MAT coalesces duplicate edges to weight 2, so the plan is NOT
    # unit-weight — the uint8 path is pinned by the ring test below.)
    _assert_plans_equal(pn, _numpy_plan(src, dst, w, nvp, base))


def test_bucket_plan_native_unit_uint8():
    """A duplicate-free unit-weight graph compresses weights to uint8 on
    both paths."""
    from cuvite_tpu.core.graph import Graph
    from cuvite_tpu.louvain.bucketed import DEFAULT_BUCKETS, _build_native

    n = 1 << 17
    s = np.arange(n, dtype=np.int64)
    g = Graph.from_edges(n, s, (s + 1) % n)
    src, dst, w, nvp, base = _slab(g)
    pn = _build_native(src, dst, w, nvp, base, widths=DEFAULT_BUCKETS)
    assert pn is not None
    assert all(b.w.dtype == np.uint8 for b in pn.buckets)
    _assert_plans_equal(pn, _numpy_plan(src, dst, w, nvp, base))


def test_bucket_plan_native_matches_numpy_weighted():
    from cuvite_tpu.io.generate import generate_rgg
    from cuvite_tpu.louvain.bucketed import DEFAULT_BUCKETS, _build_native

    src, dst, w, nvp, base = _slab(generate_rgg(1 << 15, seed=3))
    pn = _build_native(src, dst, w, nvp, base, widths=DEFAULT_BUCKETS)
    assert pn is not None
    _assert_plans_equal(pn, _numpy_plan(src, dst, w, nvp, base))
    assert all(b.w.dtype == w.dtype for b in pn.buckets)


def test_bucket_plan_native_heavy_class():
    """Hub graph: the degree-10240 vertex goes down the heavy path with
    edges in exactly the numpy order."""
    from cuvite_tpu.core.graph import Graph
    from cuvite_tpu.louvain.bucketed import DEFAULT_BUCKETS, _build_native

    edges = []
    nv = 40 * 256 + 1
    hub = nv - 1
    for c in range(40):
        b0 = c * 256
        for i in range(256):
            edges.append((b0 + i, b0 + (i + 1) % 256))
            edges.append((b0 + i, b0 + (i + 7) % 256))
    for v in range(hub):  # hub degree 10240 > DEFAULT_BUCKETS[-1]
        edges.append((hub, v))
    e = np.array(edges, dtype=np.int64)
    g = Graph.from_edges(nv, e[:, 0], e[:, 1])
    src, dst, w, nvp, base = _slab(g)
    pn = _build_native(src, dst, w, nvp, base, widths=DEFAULT_BUCKETS)
    assert pn is not None and pn.has_heavy
    _assert_plans_equal(pn, _numpy_plan(src, dst, w, nvp, base))


def test_bucket_plan_native_declines_masked_slab():
    """Color-class plans mask src mid-slab (padding not at the tail): the
    native path must decline and the numpy fallback handle it."""
    from cuvite_tpu.io.generate import generate_rmat
    from cuvite_tpu.louvain.bucketed import DEFAULT_BUCKETS, _build_native

    src, dst, w, nvp, base = _slab(generate_rmat(13, edge_factor=16, seed=2))
    src = src.copy()
    src[::3] = nvp  # mask every third edge to padding, mid-slab
    assert _build_native(src, dst, w, nvp, base,
                         widths=DEFAULT_BUCKETS) is None


@pytest.mark.parametrize("symmetrize", [True, False])
def test_build_csr_unit_matches_generic(symmetrize):
    """Unit-weight int32 builder (cv_build_csr_unit): identical CSR to the
    generic path for weights=None, duplicates counted exactly."""
    from cuvite_tpu.core.graph import Graph

    nv, ne = 257, 4096
    src, dst, _ = _random_edges(ne, nv, seed=5)
    o, t, w = native.build_csr_unit(nv, src, dst, symmetrize=symmetrize)
    old = native._LIB
    native._LIB = False
    try:
        g = Graph.from_edges(nv, src, dst, symmetrize=symmetrize)
    finally:
        native._LIB = old
    assert np.array_equal(o, g.offsets)
    assert np.array_equal(t.astype(g.tails.dtype), g.tails)
    assert np.array_equal(w.astype(g.weights.dtype), g.weights)


def test_build_csr_unit_radix_branch():
    nv = (1 << 22) + 11
    ne = 4096
    rng = np.random.default_rng(3)
    src = rng.integers(nv - 300, nv, size=ne)
    dst = rng.integers(nv - 300, nv, size=ne)
    src[: ne // 4] = src[ne // 2: ne // 2 + ne // 4]
    dst[: ne // 4] = dst[ne // 2: ne // 2 + ne // 4]
    from cuvite_tpu.core.graph import Graph

    o, t, w = native.build_csr_unit(nv, src, dst, symmetrize=True)
    old = native._LIB
    native._LIB = False
    try:
        g = Graph.from_edges(nv, src, dst, symmetrize=True)
    finally:
        native._LIB = old
    assert np.array_equal(o, g.offsets)
    assert np.array_equal(t.astype(g.tails.dtype), g.tails)
    assert np.array_equal(w.astype(g.weights.dtype), g.weights)


def test_from_edges_unit_dispatch():
    """weights=None above the size threshold must take the int32 unit path
    and produce the exact same Graph as the generic native path."""
    from cuvite_tpu.core.graph import Graph

    nv = 1 << 12
    ne = native.MIN_NATIVE_EDGES + 17
    rng = np.random.default_rng(9)
    src = rng.integers(0, nv, ne)
    dst = rng.integers(0, nv, ne)
    g_unit = Graph.from_edges(nv, src, dst)                 # unit fast path
    g_gen = Graph.from_edges(nv, src, dst,
                             weights=np.ones(ne, dtype=np.float64))
    assert np.array_equal(g_unit.offsets, g_gen.offsets)
    assert np.array_equal(g_unit.tails, g_gen.tails)
    assert np.array_equal(g_unit.weights, g_gen.weights)


def _coarsen_ref(g, dense, nc):
    """The numpy coarsen route (relabel + generic from_edges), native off."""
    from cuvite_tpu.core.graph import Graph

    old = native._LIB
    native._LIB = False
    try:
        s2 = dense[g.sources()]
        d2 = dense[g.tails.astype(np.int64)]
        return Graph.from_edges(nc, s2, d2,
                                weights=g.weights.astype(np.float64),
                                symmetrize=False)
    finally:
        native._LIB = old


@pytest.mark.parametrize("nc_target", [100, 2500])
def test_coarsen_csr_matches_numpy(nc_target):
    """cv_coarsen (small-nc dense-accumulator path) is bit-identical to
    relabel + Graph.from_edges."""
    from cuvite_tpu.core.graph import Graph
    from cuvite_tpu.coarsen.rebuild import renumber_communities

    rng = np.random.default_rng(3)
    nv, ne = 3000, 20000
    src = rng.integers(0, nv, size=ne)
    dst = rng.integers(0, nv, size=ne)
    w = rng.integers(1, 32, size=ne) / 16.0
    g = Graph.from_edges(nv, src, dst, weights=w, symmetrize=True)
    dense, nc = renumber_communities(rng.integers(0, nc_target, size=nv))
    ref = _coarsen_ref(g, dense, nc)
    off, tails, wout = native.coarsen_csr(
        g.offsets, g.tails, g.weights, dense, nc)
    assert np.array_equal(off, ref.offsets)
    assert np.array_equal(tails, ref.tails)
    assert np.array_equal(wout, ref.weights)


def test_coarsen_csr_radix_branch():
    """nc > 2^22 forces cv_coarsen's LSD-radix branch; bit-identity must
    hold there too (production coarsen of phase-0 benchmark graphs)."""
    from cuvite_tpu.core.graph import Graph
    from cuvite_tpu.coarsen.rebuild import renumber_communities

    rng = np.random.default_rng(4)
    nv, ne = 9_000_000, 120_000
    src = rng.integers(0, nv, size=ne)
    dst = rng.integers(0, nv, size=ne)
    g = Graph.from_edges(nv, src, dst, symmetrize=True)
    dense, nc = renumber_communities(rng.integers(0, 8_500_000, size=nv))
    assert nc > 1 << 22  # radix branch precondition
    ref = _coarsen_ref(g, dense, nc)
    off, tails, wout = native.coarsen_csr(
        g.offsets, g.tails, g.weights, dense, nc)
    assert np.array_equal(off, ref.offsets)
    assert np.array_equal(tails, ref.tails)
    assert np.array_equal(wout, ref.weights)


def test_coarsen_graph_dispatch():
    """coarsen_graph above the size threshold must take the native fused
    path and produce the exact same Graph as the numpy route."""
    from cuvite_tpu.core.graph import Graph
    from cuvite_tpu.coarsen.rebuild import coarsen_graph, renumber_communities

    rng = np.random.default_rng(5)
    nv = 1 << 12
    ne = native.MIN_NATIVE_EDGES + 41
    src = rng.integers(0, nv, ne)
    dst = rng.integers(0, nv, ne)
    g = Graph.from_edges(nv, src, dst)
    assert g.num_edges >= native.MIN_NATIVE_EDGES
    dense, nc = renumber_communities(rng.integers(0, 500, size=nv))
    got = coarsen_graph(g, dense, nc)
    ref = _coarsen_ref(g, dense, nc)
    assert np.array_equal(got.offsets, ref.offsets)
    assert np.array_equal(got.tails, ref.tails)
    assert np.array_equal(got.weights, ref.weights)


def test_weighted_degrees_native_matches_numpy():
    from cuvite_tpu.core.graph import Graph

    rng = np.random.default_rng(6)
    nv, ne = 5000, 70000
    src = rng.integers(0, nv, size=ne)
    dst = rng.integers(0, nv, size=ne)
    w = rng.random(ne)
    g = Graph.from_edges(nv, src, dst, weights=w, symmetrize=True)
    ref = np.bincount(g.sources(), weights=g.weights.astype(np.float64),
                      minlength=nv).astype(g.policy.weight_dtype)
    assert np.array_equal(g.weighted_degrees(), ref)


def test_distgraph_single_shard_fast_path():
    """The nshards=1 identity fast path must produce the same slabs as the
    generic remap route (checked against directly computed expectations)."""
    from cuvite_tpu.core.distgraph import DistGraph
    from cuvite_tpu.core.graph import Graph

    rng = np.random.default_rng(7)
    nv, ne = 1000, 8000
    src = rng.integers(0, nv, size=ne)
    dst = rng.integers(0, nv, size=ne)
    g = Graph.from_edges(nv, src, dst, weights=rng.random(ne))
    dg = DistGraph.build(g, 1)
    sh = dg.shards[0]
    n = g.num_edges
    assert sh.n_real_edges == n
    assert np.array_equal(sh.src[:n],
                          g.sources().astype(sh.src.dtype))
    assert np.array_equal(sh.dst[:n], g.tails.astype(sh.dst.dtype))
    assert np.array_equal(sh.w[:n], g.weights)
    assert np.all(sh.src[n:] == dg.nv_pad)
    assert np.all(sh.w[n:] == 0)
    assert np.array_equal(dg.old_to_pad, np.arange(nv))


@pytest.mark.parametrize("symmetrize", [True, False])
@pytest.mark.parametrize("id_dtype", [np.int32, np.int64])
def test_build_csr_w32_matches_generic(symmetrize, id_dtype):
    """Weighted index-payload builder (cv_build_csr_w32): identical CSR to
    the generic f64-payload path after the f32 policy cast, for both input
    id widths (no width conversion happens natively)."""
    from cuvite_tpu.core.graph import Graph

    nv, ne = 257, 4096
    src, dst, w = _random_edges(ne, nv, seed=11)
    o, t, wf = native.build_csr_w(nv, src.astype(id_dtype),
                                  dst.astype(id_dtype), w,
                                  symmetrize=symmetrize)
    g = Graph.from_edges(nv, src, dst, weights=w, symmetrize=symmetrize)
    assert np.array_equal(o, g.offsets)
    assert np.array_equal(t.astype(g.tails.dtype), g.tails)
    assert np.array_equal(wf, g.weights)


def test_build_csr_w32_radix_branch_large_nv():
    """nv > 2^22 puts the generic path on its radix branch and enables the
    from_edges w32 dispatch gate; both must agree bit-for-bit."""
    from cuvite_tpu.core.graph import Graph

    nv = (1 << 22) + 19
    ne = native.MIN_NATIVE_EDGES + 512  # also crosses the dispatch gate
    rng = np.random.default_rng(13)
    src = rng.integers(nv - 500, nv, size=ne)
    dst = rng.integers(nv - 500, nv, size=ne)
    src[: ne // 4] = src[ne // 2: ne // 2 + ne // 4]
    dst[: ne // 4] = dst[ne // 2: ne // 2 + ne // 4]
    w = rng.random(ne)
    o, t, wf = native.build_csr_w(nv, src, dst, w, symmetrize=True)
    old = native._LIB
    native._LIB = False
    try:
        g = Graph.from_edges(nv, src, dst, weights=w, symmetrize=True)
    finally:
        native._LIB = old
    assert np.array_equal(o, g.offsets)
    assert np.array_equal(t.astype(g.tails.dtype), g.tails)
    assert np.array_equal(wf, g.weights)
    # from_edges with the native lib enabled dispatches to the same path.
    g2 = Graph.from_edges(nv, src, dst, weights=w, symmetrize=True)
    assert np.array_equal(g2.weights, g.weights)
    assert np.array_equal(g2.tails, g.tails)


def test_library_name_keys_source_compiler_and_machine(monkeypatch):
    """A stale or foreign build is never loaded: the library's file name
    hashes the source, the compiler command and the machine type, and
    the target is portable (no -march=native)."""
    import os

    from cuvite_tpu import native

    assert not any(f.startswith("-march") for f in native.CXXFLAGS)
    base = native._so_path()
    assert os.path.basename(base).startswith("libcuvite_native-")
    if native.available():
        assert native._LIB._name == base
    monkeypatch.setenv("CXX", "some-other-c++")
    assert native._so_path() != base
    monkeypatch.delenv("CXX")
    monkeypatch.setattr(native.platform, "machine", lambda: "other-arch")
    assert native._so_path() != base
