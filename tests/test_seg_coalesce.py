"""The segmented coalesce (ops/segment.coalesced_runs) and its consumer
in the phase transition, coarsen/device.py::device_coarsen_slab.

A numpy lexsort + run-sum oracle pins the compacted (src, dst, w)
prefix BIT-for-bit — offsets/tails always (run presence is exact),
weights on the documented exactness domain (unit/dyadic run sums).  The
packed-sort key-width contract of ops/segment.py is pinned at its edges
here too (the widest legal 31-bit packing, the first ineligible width,
and the CUVITE_DEBUG_BOUNDS violation callback).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import cuvite_tpu.ops.segment as seg
from cuvite_tpu.ops.segment import coalesced_runs

def _slab(nv_pad, ne_pad, seed, gapped=False, self_loops=True,
          zero_weight=True):
    """A relabeled-slab-shaped triple: real rows in a prefix, padding
    (src == nv_pad, dst == 0, w == 0) after; dyadic weights (exactness
    domain).  ``gapped``: ids drawn from a sparse subset of the space
    (the renumber's hard case leaves no gaps, but coalesced_runs must
    not assume density)."""
    rng = np.random.default_rng(seed)
    n_real = ne_pad - ne_pad // 5
    pool = (rng.choice(nv_pad, size=max(nv_pad // 11, 2), replace=False)
            if gapped else np.arange(nv_pad))
    src = np.full(ne_pad, nv_pad, np.int32)
    dst = np.zeros(ne_pad, np.int32)
    w = np.zeros(ne_pad, np.float32)
    src[:n_real] = rng.choice(pool, size=n_real)
    dst[:n_real] = rng.choice(pool, size=n_real)
    if self_loops:
        src[: n_real // 8] = dst[: n_real // 8]  # heavy self-loop runs
    w[:n_real] = rng.integers(1, 64, n_real) / 8.0
    if zero_weight:
        w[n_real // 2: n_real // 2 + 37] = 0.0  # real zero-weight edges
    return tuple(jnp.asarray(x) for x in (src, dst, w))


def _lexsort_oracle(src, dst, w, nv_pad):
    """np.lexsort by (src, dst) over the real rows, one row per run at
    the run's first position, run sums in float64 cast once."""
    src, dst, w = (np.asarray(x) for x in (src, dst, w))
    real = src < nv_pad
    s, d, ww = src[real], dst[real], w[real].astype(np.float64)
    order = np.lexsort((d, s))
    s, d, ww = s[order], d[order], ww[order]
    start = np.ones(len(s), bool)
    start[1:] = (s[1:] != s[:-1]) | (d[1:] != d[:-1])
    idx = np.flatnonzero(start)
    sums = np.add.reduceat(ww, idx) if len(idx) else ww
    return s[idx], d[idx], sums.astype(np.float32)


@pytest.mark.parametrize("nv_pad,ne_pad,gapped", [
    # ≥3 slab classes; gapped (sparse) id spaces on the floor class only.
    # [floor-gapped]/[wide-slab] are tier-2 (slow): the contract they pin
    # is class-shape-invariant and [floor] keeps it in tier-1 at a third
    # of the wall; gapped-id handling stays covered in tier-1 by the
    # sticky-union/concheck gapped scenarios.
    (4096, 16384, False),
    pytest.param(4096, 16384, True, marks=pytest.mark.slow),
    pytest.param(4096, 65536, False, marks=pytest.mark.slow),
    (1024, 16384, False),
], ids=["floor", "floor-gapped", "wide-slab", "narrow-nv"])
def test_sort_coalesce_matches_lexsort_oracle(nv_pad, ne_pad, gapped):
    arrs = _slab(nv_pad, ne_pad, seed=nv_pad + ne_pad, gapped=gapped)
    src_c, dst_c, w_c, n = jax.device_get(
        coalesced_runs(*arrs, nv_pad=nv_pad))
    s_ref, d_ref, w_ref = _lexsort_oracle(*arrs, nv_pad)
    n = int(n)
    assert n == len(s_ref)
    assert np.array_equal(src_c[:n], s_ref)
    assert np.array_equal(dst_c[:n], d_ref)
    assert np.array_equal(w_c[:n], w_ref)
    # Tail sentinel contract: padding after the compacted prefix.
    assert (src_c[n:] == nv_pad).all()
    assert (dst_c[n:] == 0).all()
    assert (w_c[n:] == 0).all()


@pytest.mark.parametrize("case", ["all-padding", "one-run", "no-padding"])
def test_coalesce_degenerate_slabs(case):
    """The slabs at the edges of the contract: nothing real (n == 0,
    all sentinel), every real row one (src, dst) pair (one summed run),
    and a slab with no padding row at all."""
    nv_pad, ne_pad = 1024, 4096
    rng = np.random.default_rng(11)
    src = np.full(ne_pad, nv_pad, np.int32)
    dst = np.zeros(ne_pad, np.int32)
    w = np.zeros(ne_pad, np.float32)
    if case == "one-run":
        src[:999], dst[:999] = 7, 3
        w[:999] = rng.integers(1, 8, 999) / 4.0
    elif case == "no-padding":
        src[:] = rng.integers(0, nv_pad, ne_pad)
        dst[:] = rng.integers(0, nv_pad, ne_pad)
        w[:] = rng.integers(1, 8, ne_pad) / 4.0
    arrs = tuple(jnp.asarray(x) for x in (src, dst, w))
    src_c, dst_c, w_c, n = jax.device_get(
        coalesced_runs(*arrs, nv_pad=nv_pad))
    s_ref, d_ref, w_ref = _lexsort_oracle(src, dst, w, nv_pad)
    n = int(n)
    assert n == len(s_ref) == {"all-padding": 0, "one-run": 1}.get(case, n)
    assert np.array_equal(src_c[:n], s_ref)
    assert np.array_equal(dst_c[:n], d_ref)
    assert np.array_equal(w_c[:n], w_ref)
    assert (src_c[n:] == nv_pad).all() and (w_c[n:] == 0).all()


def test_zero_weight_runs_emitted_by_presence():
    """A real zero-weight edge is a run (presence, not weight) —
    dropping it would change the coarse offsets."""
    nv_pad, ne_pad = 1024, 16384
    src = np.full(ne_pad, nv_pad, np.int32)
    dst = np.zeros(ne_pad, np.int32)
    w = np.zeros(ne_pad, np.float32)
    src[:3] = [5, 7, 9]
    dst[:3] = [6, 8, 10]
    w[:3] = [1.0, 0.0, 2.0]  # the (7, 8) run weighs exactly 0
    arrs = tuple(jnp.asarray(x) for x in (src, dst, w))
    src_c, dst_c, w_c, n = jax.device_get(
        coalesced_runs(*arrs, nv_pad=nv_pad))
    assert int(n) == 3
    assert list(src_c[:3]) == [5, 7, 9] and w_c[1] == 0.0


def test_device_coarsen_slab_precomputed_renumber_matches_host(two_cliques):
    """Through the real consumer: device_coarsen_slab handed a
    precomputed renumber (the fused driver's call) returns the identical
    6-tuple as the self-renumbering call, and its coarse graph is the
    host coarsen_graph's."""
    from cuvite_tpu.coarsen.device import device_coarsen_slab, device_renumber
    from cuvite_tpu.coarsen.rebuild import coarsen_graph, renumber_communities
    from cuvite_tpu.core.distgraph import DistGraph

    dg = DistGraph.build(two_cliques, 1)
    sh = dg.shards[0]
    lab = np.arange(dg.nv_pad, dtype=np.int64)
    lab[:5] = 0
    lab[5:10] = 5
    comm = jnp.asarray(lab.astype(np.asarray(sh.src).dtype))
    mask = jnp.asarray(dg.vertex_mask())
    args = (jnp.asarray(np.asarray(sh.src)), jnp.asarray(np.asarray(sh.dst)),
            jnp.asarray(np.asarray(sh.w)), comm, mask)
    ref = jax.device_get(device_coarsen_slab(*args, nv_pad=dg.nv_pad))
    dmap, nc = device_renumber(comm, mask, nv_pad=dg.nv_pad)
    got = jax.device_get(device_coarsen_slab(*args, nv_pad=dg.nv_pad,
                                             dense_map=dmap, nc=nc))
    for r, g in zip(ref, got):
        assert np.array_equal(r, g)
    src2, dst2, w2, _dm, nc2, ne2 = got
    dense, nc_h = renumber_communities(lab[dg.old_to_pad])
    gh = coarsen_graph(two_cliques, dense, nc_h)
    assert int(nc2) == nc_h and int(ne2) == gh.num_edges
    assert np.array_equal(src2[:int(ne2)], gh.sources())
    assert np.array_equal(dst2[:int(ne2)], gh.tails)
    assert np.array_equal(w2[:int(ne2)], gh.weights)


def test_ds32_sort_fallback_matches_plain_on_exact_domain():
    """On dyadic weights the ds32 pair sums, collapsed once, equal the
    plain f32 run sums bit-for-bit."""
    arrs = _slab(1024, 16384, seed=9)
    a = jax.device_get(coalesced_runs(*arrs, nv_pad=1024))
    b = jax.device_get(coalesced_runs(*arrs, nv_pad=1024,
                                      accum_dtype=seg.DS_ACCUM))
    for x, y in zip(a, b):
        assert np.array_equal(x, y)


# ---------------------------------------------------------------------------
# Full-run integration: the sort engine's device transition compiles its
# coalesce once per slab class and adds no device syncs to a phase.


@pytest.fixture(scope="module")
def rmat10():
    from cuvite_tpu.io.generate import generate_rmat

    g = generate_rmat(10, edge_factor=8, seed=3)
    assert g.num_vertices <= 4096 and g.num_edges <= 16384  # floor class
    return g


def test_coalesce_compiles_once_per_slab_class(rmat10, monkeypatch):
    """Every phase of this run shares the floor slab class, so the
    device transition's relabel+coalesce program serves every phase
    from at most one new compiled entry."""
    import cuvite_tpu.louvain.driver as drv

    calls = []
    orig = drv.device_coarsen_slab

    def spy(*a, **k):
        calls.append(k["nv_pad"])
        return orig(*a, **k)

    monkeypatch.setattr(drv, "device_coarsen_slab", spy)
    before = orig._cache_size()
    res = drv.louvain_phases(rmat10, engine="sort")
    assert len(res.phases) >= 3 and len(calls) >= 3
    assert len(set(calls)) == 1
    assert orig._cache_size() - before <= 1


def test_device_transition_adds_no_device_syncs(rmat10, monkeypatch):
    """One sync per phase stays one sync per phase: the device
    transition makes as many jax.device_get calls as the host transition
    (CUVITE_DEVICE_COARSEN=0), and clusters identically."""
    from cuvite_tpu.louvain.driver import louvain_phases

    def run_counting():
        calls = []
        orig = jax.device_get

        def spy(x):
            calls.append(1)
            return orig(x)

        monkeypatch.setattr(jax, "device_get", spy)
        try:
            res = louvain_phases(rmat10, engine="sort")
        finally:
            monkeypatch.setattr(jax, "device_get", orig)
        return len(calls), res

    monkeypatch.delenv("CUVITE_DEVICE_COARSEN", raising=False)
    n0, r0 = run_counting()
    monkeypatch.setenv("CUVITE_DEVICE_COARSEN", "0")
    n1, r1 = run_counting()
    assert np.array_equal(r0.communities, r1.communities)
    assert n0 == n1 >= len(r0.phases)


def test_coalesce_stage_and_edge_counter(rmat10):
    """coalesce_s splits out of coarsen_s (schema v4), and the
    coalesce_edges counter sums the edges of every coarsened phase."""
    from cuvite_tpu.louvain.driver import louvain_phases
    from cuvite_tpu.utils.trace import Tracer

    tr = Tracer()
    res = louvain_phases(rmat10, engine="sort", tracer=tr)
    bd = tr.breakdown()
    assert "coalesce_s" in bd and 0 < bd["coalesce_s"] <= bd["coarsen_s"]
    assert tr.counters["coalesce_edges"] \
        == sum(p.num_edges for p in res.phases) > 0


# ---------------------------------------------------------------------------
# Packed-sort key-width contract (ops/segment.py): the coalesce
# chokepoint's edges, pinned (ISSUE 8 satellite).


def _lex_oracle(src, ckey, w):
    order = np.lexsort((np.asarray(ckey), np.asarray(src)))
    return (np.asarray(src)[order], np.asarray(ckey)[order],
            np.asarray(w)[order])


def test_packed_sort_widest_legal_31bit_packing():
    """kbits + sbits == 31 is the widest int32 packing: the top packed
    key is INT32_MAX and must NOT flip the sign bit (segment.py:120).
    Extreme ids at both bounds pin the boundary."""
    rng = np.random.default_rng(2)
    src_bound, key_bound = 1 << 16, 1 << 15   # sbits 16 + kbits 15 == 31
    n = 4096
    src = rng.integers(0, src_bound, n).astype(np.int32)
    ckey = rng.integers(0, key_bound, n).astype(np.int32)
    # Force the extremes: the (max src, max key) row packs to INT32_MAX.
    src[:4] = [src_bound - 1, src_bound - 1, 0, 0]
    ckey[:4] = [key_bound - 1, 0, key_bound - 1, 0]
    w = rng.random(n).astype(np.float32)
    out = jax.device_get(seg.sort_edges_by_vertex_comm(
        jnp.asarray(src), jnp.asarray(ckey), jnp.asarray(w),
        src_bound=src_bound, key_bound=key_bound))
    s_ref, c_ref, _ = _lex_oracle(src, ckey, w)
    assert np.array_equal(out[0], s_ref)
    assert np.array_equal(out[1], c_ref)
    # The last row really is the INT32_MAX packing.
    assert int(out[0][-1]) == src_bound - 1 \
        and int(out[1][-1]) == key_bound - 1


def test_packed_sort_first_ineligible_width_falls_back_correctly():
    """kbits + sbits == 32: one bit past the int32 packing — without
    x64 the sort must take the lexicographic path and still produce the
    exact (src, ckey) order."""
    rng = np.random.default_rng(3)
    src_bound, key_bound = 1 << 16, 1 << 16   # 16 + 16 == 32
    n = 4096
    src = rng.integers(0, src_bound, n).astype(np.int32)
    ckey = rng.integers(0, key_bound, n).astype(np.int32)
    src[:2] = [src_bound - 1, 0]
    ckey[:2] = [key_bound - 1, key_bound - 1]
    w = rng.random(n).astype(np.float32)
    out = jax.device_get(seg.sort_edges_by_vertex_comm(
        jnp.asarray(src), jnp.asarray(ckey), jnp.asarray(w),
        src_bound=src_bound, key_bound=key_bound))
    s_ref, c_ref, _ = _lex_oracle(src, ckey, w)
    assert np.array_equal(out[0], s_ref)
    assert np.array_equal(out[1], c_ref)


@pytest.mark.parametrize("bad", ["src", "ckey"])
def test_packed_sort_bound_violation_callback(bad, monkeypatch):
    """CUVITE_DEBUG_BOUNDS: an id at or above its declared bound trips
    the host callback loudly (a silently corrupted packing would sort
    rows to the FRONT — segment.py's documented failure mode)."""
    monkeypatch.setattr(seg, "DEBUG_BOUNDS", True)
    src = np.array([1, 2, 3], np.int32)
    ckey = np.array([0, 1, 2], np.int32)
    if bad == "src":
        src[0] = 4       # == src_bound
    else:
        ckey[0] = 5      # > key_bound
    w = np.ones(3, np.float32)
    with pytest.raises(AssertionError, match="bound violation"):
        out = seg.sort_edges_by_vertex_comm(
            jnp.asarray(src), jnp.asarray(ckey), jnp.asarray(w),
            src_bound=4, key_bound=4)
        jax.block_until_ready(out)

# ---------------------------------------------------------------------------
# ISSUE 16: the boundary trio generalized from the bare sort to the
# coalesce CHOKEPOINT (coalesced_runs rides the packed sort at
# src_bound = nv_pad + 1, key_bound = nv_pad, so nv_pad = 2^15 is the
# widest int32 packing and 2^16 the first ineligible width), plus the
# tier-6 raise-guard.


def _chokepoint_slab(nv_pad, ne_pad, seed):
    """Slab with the extreme (nv_pad-1, nv_pad-1) packing duplicated so
    coalescing must SUM across the widest key, dyadic weights (exact)."""
    rng = np.random.default_rng(seed)
    n_real = ne_pad - ne_pad // 7
    src = np.full(ne_pad, nv_pad, np.int32)
    dst = np.zeros(ne_pad, np.int32)
    w = np.zeros(ne_pad, np.float32)
    src[:n_real] = rng.integers(0, nv_pad, n_real)
    dst[:n_real] = rng.integers(0, nv_pad, n_real)
    src[:4] = [nv_pad - 1, nv_pad - 1, 0, 0]
    dst[:4] = [nv_pad - 1, nv_pad - 1, nv_pad - 1, 0]
    w[:n_real] = rng.integers(1, 64, n_real) / 8.0
    return src, dst, w


def _coalesce_oracle(src, ckey, w, nv_pad):
    """Sorted-unique real (src, ckey) pairs with summed weights, in
    float64 (the dyadic inputs make every f32 partial sum exact, so the
    coalesce must match BIT-for-bit after the cast)."""
    src, ckey, w = (np.asarray(x) for x in (src, ckey, w))
    real = src < nv_pad
    keys = src[real].astype(np.int64) * nv_pad + ckey[real]
    order = np.argsort(keys, kind="stable")
    ks, ws = keys[order], w[real][order].astype(np.float64)
    uniq, start = np.unique(ks, return_index=True)
    sums = np.add.reduceat(ws, start)
    return ((uniq // nv_pad).astype(np.int32),
            (uniq % nv_pad).astype(np.int32),
            sums.astype(np.float32))


def _assert_coalesce_matches_oracle(out, src, dst, w, nv_pad):
    s_ref, c_ref, w_ref = _coalesce_oracle(src, dst, w, nv_pad)
    src_c, ckey_c, w_c, n = (np.asarray(x) for x in jax.device_get(out))
    n = int(n)
    assert n == len(s_ref)
    assert np.array_equal(src_c[:n], s_ref)
    assert np.array_equal(ckey_c[:n], c_ref)
    assert np.array_equal(w_c[:n], w_ref)
    assert (src_c[n:] == nv_pad).all()


def test_coalesce_chokepoint_widest_legal_31bit_packing():
    """nv_pad = 2^15: sbits(nv_pad + 1) = 16 + kbits(nv_pad) = 15 == 31,
    the widest int32 packing the chokepoint ever rides — the duplicated
    (nv_pad-1, nv_pad-1) rows pack to the top key and must still
    coalesce to ONE summed run, not sort to the front."""
    nv_pad, ne_pad = 1 << 15, 8192
    src, dst, w = _chokepoint_slab(nv_pad, ne_pad, seed=31)
    out = coalesced_runs(jnp.asarray(src), jnp.asarray(dst),
                         jnp.asarray(w), nv_pad=nv_pad)
    _assert_coalesce_matches_oracle(out, src, dst, w, nv_pad)


def test_coalesce_chokepoint_first_ineligible_width():
    """nv_pad = 2^16: 17 + 16 == 33 bits — the chokepoint must take the
    lexicographic fallback and still produce the exact coalesce."""
    nv_pad, ne_pad = 1 << 16, 8192
    src, dst, w = _chokepoint_slab(nv_pad, ne_pad, seed=32)
    out = coalesced_runs(jnp.asarray(src), jnp.asarray(dst),
                         jnp.asarray(w), nv_pad=nv_pad)
    _assert_coalesce_matches_oracle(out, src, dst, w, nv_pad)


def test_coalesce_chokepoint_forced_64_bit_identical():
    """Under jax_enable_x64 the same ineligible width packs into ONE
    int64 key — and the coalesced result must be bit-identical to the
    lexicographic run (the packed/lex parity contract, at the
    chokepoint rather than the bare sort)."""
    nv_pad, ne_pad = 1 << 16, 8192
    src, dst, w = _chokepoint_slab(nv_pad, ne_pad, seed=33)
    arrs = tuple(jnp.asarray(x) for x in (src, dst, w))
    base = jax.device_get(coalesced_runs(*arrs, nv_pad=nv_pad))
    prior = jax.config.jax_enable_x64
    try:
        jax.config.update("jax_enable_x64", True)
        forced = jax.device_get(coalesced_runs(*arrs, nv_pad=nv_pad))
    finally:
        jax.config.update("jax_enable_x64", prior)
    for b, f, name in zip(base, forced, ("src", "ckey", "w", "n")):
        assert np.array_equal(np.asarray(b), np.asarray(f)), name


def test_slab_ne_max_raise_guard():
    """The widest legal slab traces; one doubling past SLAB_NE_MAX
    fails LOUD (the int32 run-id cumsums would wrap silently)."""
    def probe(ne):
        jax.eval_shape(
            lambda s, c, w: coalesced_runs(s, c, w, nv_pad=1 << 12),
            jax.ShapeDtypeStruct((ne,), jnp.int32),
            jax.ShapeDtypeStruct((ne,), jnp.int32),
            jax.ShapeDtypeStruct((ne,), jnp.float32))

    probe(seg.SLAB_NE_MAX)
    with pytest.raises(ValueError, match="SLAB_NE_MAX"):
        probe(seg.SLAB_NE_MAX * 2)
    with pytest.raises(ValueError, match="SLAB_NE_MAX"):
        jax.eval_shape(
            seg.run_totals,
            jax.ShapeDtypeStruct((seg.SLAB_NE_MAX * 2,), jnp.float32),
            jax.ShapeDtypeStruct((seg.SLAB_NE_MAX * 2,), jnp.bool_))
