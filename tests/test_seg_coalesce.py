"""Segmented-coalesce engines (ISSUE 8): kernels/seg_coalesce.py +
ops/segment.coalesced_runs + the device_coarsen_slab dispatch.

The packed-sort path is the bit-parity oracle: the dense dst-tile
engine (the XLA scatter bin-accumulate) must reproduce its compacted (src, dst, w) prefix BIT-for-bit —
offsets/tails always (run presence is exact in every mode), weights on
the documented exactness domain (unit/dyadic run sums).  The
packed-sort key-width contract of ops/segment.py is pinned at its
edges here too (the widest legal 31-bit packing, the first ineligible
width, and the CUVITE_DEBUG_BOUNDS violation callback).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import cuvite_tpu.ops.segment as seg
from cuvite_tpu.kernels.seg_coalesce import coalesce_engine
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


@pytest.mark.parametrize("nv_pad,ne_pad,gapped", [
    # ≥3 slab classes; gapped (sparse) id spaces on the floor class only
    # — id sparsity is engine-invariant, one class covers it.
    # [floor-gapped]/[wide-slab] are tier-2 (slow): the identity they
    # pin is class-shape-invariant and [floor] keeps it in tier-1 at a
    # third of the wall; gapped-id handling stays covered in tier-1 by
    # the sticky-union/concheck gapped scenarios.
    (4096, 16384, False),
    pytest.param(4096, 16384, True, marks=pytest.mark.slow),
    pytest.param(4096, 65536, False, marks=pytest.mark.slow),
    (1024, 16384, False),
], ids=["floor", "floor-gapped", "wide-slab", "narrow-nv"])
def test_dense_engines_bit_identical_to_sort(nv_pad, ne_pad, gapped):
    arrs = _slab(nv_pad, ne_pad, seed=nv_pad + ne_pad, gapped=gapped)
    ref = jax.device_get(coalesced_runs(*arrs, nv_pad=nv_pad,
                                        engine="sort"))
    got = jax.device_get(coalesced_runs(*arrs, nv_pad=nv_pad,
                                        engine="xla"))
    for r, g, name in zip(ref, got, ("src", "dst", "w", "n")):
        assert np.array_equal(r, g), name
    # Tail sentinel contract: padding after the compacted prefix.
    src_c, dst_c, w_c, n = ref
    n = int(n)
    assert (src_c[n:] == nv_pad).all()
    assert (dst_c[n:] == 0).all()
    assert (w_c[n:] == 0).all()
    # The prefix is strictly (src, dst)-sorted: distinct packed keys.
    keys = src_c[:n].astype(np.int64) * nv_pad + dst_c[:n]
    assert (np.diff(keys) > 0).all()


def test_zero_weight_runs_emitted_by_presence():
    """A real zero-weight edge is a run (presence, not weight) in every
    engine — dropping it would change the coarse offsets."""
    nv_pad, ne_pad = 1024, 16384
    src = np.full(ne_pad, nv_pad, np.int32)
    dst = np.zeros(ne_pad, np.int32)
    w = np.zeros(ne_pad, np.float32)
    src[:3] = [5, 7, 9]
    dst[:3] = [6, 8, 10]
    w[:3] = [1.0, 0.0, 2.0]  # the (7, 8) run weighs exactly 0
    arrs = tuple(jnp.asarray(x) for x in (src, dst, w))
    for engine in ("sort", "xla"):
        src_c, dst_c, w_c, n = jax.device_get(
            coalesced_runs(*arrs, nv_pad=nv_pad, engine=engine))
        assert int(n) == 3, engine
        assert list(src_c[:3]) == [5, 7, 9] and w_c[1] == 0.0, engine


def test_device_coarsen_slab_dense_vs_sort_bitwise(two_cliques):
    """Through the real consumer: device_coarsen_slab with the dense
    engines produces the identical 6-tuple (slab, dense_map, nc, ne2)."""
    from cuvite_tpu.coarsen.device import device_coarsen_slab
    from cuvite_tpu.core.distgraph import DistGraph

    dg = DistGraph.build(two_cliques, 1)
    sh = dg.shards[0]
    lab = np.arange(dg.nv_pad, dtype=np.int64)
    lab[:5] = 0
    lab[5:10] = 5
    args = (jnp.asarray(np.asarray(sh.src)), jnp.asarray(np.asarray(sh.dst)),
            jnp.asarray(np.asarray(sh.w)),
            jnp.asarray(lab.astype(np.asarray(sh.src).dtype)),
            jnp.asarray(dg.vertex_mask()))
    ref = jax.device_get(device_coarsen_slab(*args, nv_pad=dg.nv_pad,
                                             coalesce="sort"))
    got = jax.device_get(device_coarsen_slab(*args, nv_pad=dg.nv_pad,
                                             coalesce="xla"))
    for r, g in zip(ref, got):
        assert np.array_equal(r, g)


def test_coalesce_engine_policy(monkeypatch):
    monkeypatch.delenv("CUVITE_SEG_COALESCE", raising=False)
    # Default: the packed sort stays the workhorse until the staged chip
    # A/B promotes a dense engine (measured rationale in the module).
    assert coalesce_engine(4096) == "sort"
    monkeypatch.setenv("CUVITE_SEG_COALESCE", "xla")
    assert coalesce_engine(4096) == "xla"
    # ds32 run sums need the sorted pair arithmetic — degrade in every
    # mode.
    assert coalesce_engine(4096, seg.DS_ACCUM) == "sort"
    # Domain over the accumulator budget (nv_pad > MAX_NV) -> degrade.
    assert coalesce_engine(1 << 16) == "sort"
    monkeypatch.setenv("CUVITE_SEG_COALESCE_MAX_NV", "1024")
    assert coalesce_engine(4096) == "sort"
    assert coalesce_engine(1024) == "xla"
    monkeypatch.delenv("CUVITE_SEG_COALESCE_MAX_NV")
    # The Pallas mode is gone: it warns and keeps the default.
    monkeypatch.setenv("CUVITE_SEG_COALESCE", "pallas")
    with pytest.warns(UserWarning, match="unrecognized"):
        assert coalesce_engine(4096) == "sort"
    monkeypatch.setenv("CUVITE_SEG_COALESCE", "0")
    assert coalesce_engine(1024) == "sort"
    # A typo'd pin warns and keeps the default instead of silently
    # measuring the wrong engine.
    monkeypatch.setenv("CUVITE_SEG_COALESCE", "sorr")
    with pytest.warns(UserWarning, match="unrecognized"):
        assert coalesce_engine(1024) == "sort"


def test_coalesced_runs_rejects_ds32_on_dense():
    arrs = _slab(1024, 16384, seed=1)
    with pytest.raises(AssertionError, match="ds32"):
        coalesced_runs(*arrs, nv_pad=1024, accum_dtype=seg.DS_ACCUM,
                       engine="xla")


def test_ds32_sort_fallback_matches_plain_on_exact_domain():
    """ds32 always rides the sort path; on dyadic weights its collapsed
    run sums equal the plain f32 path bit-for-bit."""
    arrs = _slab(1024, 16384, seed=9)
    a = jax.device_get(coalesced_runs(*arrs, nv_pad=1024, engine="sort"))
    b = jax.device_get(coalesced_runs(*arrs, nv_pad=1024, engine="sort",
                                      accum_dtype=seg.DS_ACCUM))
    for x, y in zip(a, b):
        assert np.array_equal(x, y)


# ---------------------------------------------------------------------------
# Full-run integration: the sort engine's device transition with a dense
# coalesce forced must cluster bit-identically, with zero fresh compiles
# on phases 2+ and the same per-phase sync count as the default path.


@pytest.fixture(scope="module")
def rmat10():
    from cuvite_tpu.io.generate import generate_rmat

    g = generate_rmat(10, edge_factor=8, seed=3)
    assert g.num_vertices <= 4096 and g.num_edges <= 16384  # floor class
    return g


def test_sort_engine_dense_coalesce_full_run_identical(rmat10, monkeypatch):
    from cuvite_tpu.louvain.driver import louvain_phases

    monkeypatch.delenv("CUVITE_SEG_COALESCE", raising=False)
    r0 = louvain_phases(rmat10, engine="sort")
    monkeypatch.setenv("CUVITE_SEG_COALESCE", "xla")
    r1 = louvain_phases(rmat10, engine="sort")
    assert len(r0.phases) == len(r1.phases) >= 3
    assert r0.total_iterations == r1.total_iterations
    assert r0.modularity == r1.modularity
    assert np.array_equal(r0.communities, r1.communities)


def test_fused_dense_coalesce_full_run_identical(rmat10, monkeypatch):
    import cuvite_tpu.louvain.driver as drv
    from cuvite_tpu.louvain.driver import louvain_phases

    # Force the one-call-per-phase multilevel path so device_coarsen_slab
    # actually runs between fused calls.
    monkeypatch.setattr(drv, "FUSED_SHRINK_EDGES", 1 << 10)
    monkeypatch.delenv("CUVITE_SEG_COALESCE", raising=False)
    r0 = louvain_phases(rmat10, engine="fused")
    monkeypatch.setenv("CUVITE_SEG_COALESCE", "xla")
    r1 = louvain_phases(rmat10, engine="fused")
    assert len(r0.phases) == len(r1.phases) >= 3
    assert np.array_equal(r0.communities, r1.communities)


def test_dense_coalesce_zero_fresh_compiles_after_phase1(
        rmat10, monkeypatch):
    """The dense path must keep the tentpole compile contract: same pow2
    class across phases => all compiles in phases 0-1, none after."""
    import logging

    from cuvite_tpu.louvain.driver import louvain_phases
    from cuvite_tpu.utils.trace import Tracer

    monkeypatch.setenv("CUVITE_SEG_COALESCE", "xla")
    compiles = []

    class _Grab(logging.Handler):
        def emit(self, record):
            if "Compiling" in record.getMessage():
                compiles.append(record.getMessage())

    import contextlib

    class _Probe(Tracer):
        def __init__(self):
            super().__init__(enabled=True)
            self.marks = []

        @contextlib.contextmanager
        def stage(self, name):
            if name == "iterate":
                self.marks.append(len(compiles))
            with super().stage(name):
                yield

    probe = _Probe()
    handler = _Grab(level=logging.WARNING)
    logger = logging.getLogger("jax")
    logger.addHandler(handler)
    jax.config.update("jax_log_compiles", True)
    try:
        res = louvain_phases(rmat10, engine="sort", tracer=probe)
    finally:
        jax.config.update("jax_log_compiles", False)
        logger.removeHandler(handler)
    assert len(res.phases) >= 3 and len(probe.marks) >= 3
    fresh_after_phase1 = len(compiles) - probe.marks[2]
    assert fresh_after_phase1 == 0, compiles[probe.marks[2]:][:4]


def test_dense_coalesce_adds_no_device_syncs(rmat10, monkeypatch):
    """One sync per phase stays one sync per phase: forcing the dense
    coalesce must not change the run's jax.device_get call count."""
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

    monkeypatch.delenv("CUVITE_SEG_COALESCE", raising=False)
    n0, r0 = run_counting()
    monkeypatch.setenv("CUVITE_SEG_COALESCE", "xla")
    n1, r1 = run_counting()
    assert np.array_equal(r0.communities, r1.communities)
    assert n0 == n1


def test_coalesce_stage_and_coverage_counters(rmat10, monkeypatch):
    """coalesce_s splits out of coarsen_s (schema v4) and the coverage
    counters say which engine ran: 0 dense edges by default, all of
    them with the dense engine forced."""
    from cuvite_tpu.louvain.driver import louvain_phases
    from cuvite_tpu.utils.trace import Tracer

    monkeypatch.delenv("CUVITE_SEG_COALESCE", raising=False)
    tr = Tracer()
    louvain_phases(rmat10, engine="sort", tracer=tr)
    bd = tr.breakdown()
    assert "coalesce_s" in bd and 0 < bd["coalesce_s"] <= bd["coarsen_s"]
    assert tr.counters.get("coalesce_edges", 0) > 0
    assert tr.counters.get("coalesce_dense_edges", 0) == 0
    tr2 = Tracer()
    monkeypatch.setenv("CUVITE_SEG_COALESCE", "xla")
    louvain_phases(rmat10, engine="sort", tracer=tr2)
    assert tr2.counters["coalesce_dense_edges"] \
        == tr2.counters["coalesce_edges"] > 0


# ---------------------------------------------------------------------------
# Packed-sort key-width contract (ops/segment.py): the fallback
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
# coalesce CHOKEPOINT (coalesced_runs engine='sort' rides the packed
# sort at src_bound = nv_pad + 1, key_bound = nv_pad, so nv_pad = 2^15
# is the widest int32 packing and 2^16 the first ineligible width),
# plus the heavy-layout elems budget and the tier-6 raise-guards.


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
    engine must match BIT-for-bit after the cast)."""
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
                         jnp.asarray(w), nv_pad=nv_pad, engine="sort")
    _assert_coalesce_matches_oracle(out, src, dst, w, nv_pad)


def test_coalesce_chokepoint_first_ineligible_width():
    """nv_pad = 2^16: 17 + 16 == 33 bits — the chokepoint must take the
    lexicographic fallback and still produce the exact coalesce."""
    nv_pad, ne_pad = 1 << 16, 8192
    src, dst, w = _chokepoint_slab(nv_pad, ne_pad, seed=32)
    out = coalesced_runs(jnp.asarray(src), jnp.asarray(dst),
                         jnp.asarray(w), nv_pad=nv_pad, engine="sort")
    _assert_coalesce_matches_oracle(out, src, dst, w, nv_pad)


def test_coalesce_chokepoint_forced_64_bit_identical():
    """Under jax_enable_x64 the same ineligible width packs into ONE
    int64 key — and the coalesced result must be bit-identical to the
    lexicographic run (the packed/lex parity contract, at the
    chokepoint rather than the bare sort)."""
    nv_pad, ne_pad = 1 << 16, 8192
    src, dst, w = _chokepoint_slab(nv_pad, ne_pad, seed=33)
    arrs = tuple(jnp.asarray(x) for x in (src, dst, w))
    base = jax.device_get(coalesced_runs(*arrs, nv_pad=nv_pad,
                                         engine="sort"))
    prior = jax.config.jax_enable_x64
    try:
        jax.config.update("jax_enable_x64", True)
        forced = jax.device_get(coalesced_runs(*arrs, nv_pad=nv_pad,
                                               engine="sort"))
    finally:
        jax.config.update("jax_enable_x64", prior)
    for b, f, name in zip(base, forced, ("src", "ckey", "w", "n")):
        assert np.array_equal(np.asarray(b), np.asarray(f)), name


def test_slab_ne_max_raise_guard():
    """The widest legal slab traces; one doubling past SLAB_NE_MAX
    fails LOUD (the int32 run-id cumsums would wrap silently)."""
    def probe(ne):
        jax.eval_shape(
            lambda s, c, w: coalesced_runs(s, c, w, nv_pad=1 << 12,
                                           engine="sort"),
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


def test_flat_nv_max_raise_guard():
    """seg_coalesce_xla's flat (src << kbits) | dst key: FLAT_NV_MAX
    traces, one doubling past raises (the key would wrap int32)."""
    from cuvite_tpu.kernels.seg_coalesce import (FLAT_NV_MAX,
                                                 seg_coalesce_xla)

    def probe(nv):
        jax.eval_shape(
            lambda s, d, w: seg_coalesce_xla(s, d, w, nv_pad=nv),
            jax.ShapeDtypeStruct((4096,), jnp.int32),
            jax.ShapeDtypeStruct((4096,), jnp.int32),
            jax.ShapeDtypeStruct((4096,), jnp.float32))

    probe(FLAT_NV_MAX)
    with pytest.raises(ValueError, match="FLAT_NV_MAX"):
        probe(FLAT_NV_MAX * 2)


def test_heavy_layout_elems_budget_boundary():
    """build_heavy_layout's eligibility boundary: a layout landing
    exactly ON max_elems is returned; one element past degrades to None
    (the caller keeps the sorted path, with coverage accounting)."""
    from cuvite_tpu.kernels.heavy_bincount import build_heavy_layout

    nv_local = 16
    src = np.repeat(np.arange(8, dtype=np.int32), 8)   # 8 hubs, deg 8
    dst = np.tile(np.arange(8, dtype=np.int32), 8)
    w = np.ones(64, np.float32)
    # H = 8 -> Hp = 8; counts.max() = 8, d_chunk = 8 -> D = 8: 64 elems.
    at = build_heavy_layout(src, dst, w, nv_local=nv_local,
                            pad_id=nv_local, d_chunk=8, max_elems=64)
    assert at is not None
    verts, dstT, wT = at
    assert verts.shape == (8,) and dstT.shape == (8, 8)
    past = build_heavy_layout(src, dst, w, nv_local=nv_local,
                              pad_id=nv_local, d_chunk=8, max_elems=63)
    assert past is None
