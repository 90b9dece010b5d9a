"""Device re-binning (ISSUE 19): coarsen/rebin.py + the driver/batched
integration, and the coalesce at the 31-bit packing boundary.

The host ``BucketPlan.build`` is the bit-identity oracle: the device
plan builder must reproduce its buckets (verts/dst/w prefix per kept
width), self-loop vector and assemble permutation exactly, on gapped
label spaces and across every ladder width the class admits.  The
integration half pins the serving properties the tentpole claims: full
sort/bucketed/batched runs label-identical with device re-binning
forced on and off, zero fresh compiles on phases >= 2 of an unchanged
class, the one-sync-per-phase discipline intact on re-binned phases,
and NO host BucketPlan.build call after phase 0.
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cuvite_tpu.coarsen.rebin import (
    device_rebin_plan,
    rebin_eligible,
    rebin_geometry,
    sized_geometry,
)
from cuvite_tpu.io.generate import generate_rmat
from cuvite_tpu.louvain.bucketed import (
    DEFAULT_BUCKETS,
    BucketPlan,
    build_assemble_perm,
)
from cuvite_tpu.louvain.driver import louvain_phases
from cuvite_tpu.ops.segment import coalesced_runs

# ---------------------------------------------------------------------------
# Plan bit-identity vs the host oracle


def _coalesced_slab(rng, nv_pad, ne_pad, *, base=0, gapped=False,
                    hubs=0, hub_deg=None, max_deg=8):
    """A slab honoring the rebin_plan contract: sorted by src, distinct
    (src, dst) pairs, real rows compacted into the prefix, padding
    (src == nv_pad, w == 0) after; dyadic weights (exactness domain).
    ``gapped``: only a sparse subset of the label space has edges.
    ``hubs``: that many vertices get degree ``hub_deg`` (default
    nv_pad, the widest class) — the all-eligible-widths lever."""
    deg = rng.integers(0, max_deg + 1, nv_pad)
    if gapped:
        dead = rng.choice(nv_pad, size=nv_pad - nv_pad // 7, replace=False)
        deg[dead] = 0
    if hubs:
        hub_ids = rng.choice(np.flatnonzero(deg >= 0), size=hubs,
                             replace=False)
        deg[hub_ids] = nv_pad if hub_deg is None else hub_deg
    assert int(deg.sum()) <= ne_pad, "slab budget"
    src_l, dst_l = [], []
    for v in range(nv_pad):
        d = int(deg[v])
        if not d:
            continue
        nbrs = np.sort(rng.permutation(nv_pad)[:d])
        src_l.append(np.full(d, v, np.int64))
        dst_l.append(nbrs + base)
    n = int(deg.sum())
    src = np.full(ne_pad, nv_pad, np.int32)
    dst = np.zeros(ne_pad, np.int32)
    w = np.zeros(ne_pad, np.float32)
    if n:
        src[:n] = np.concatenate(src_l)
        dst[:n] = np.concatenate(dst_l)
        w[:n] = rng.integers(1, 64, n) / 8.0
    return src, dst, w


@pytest.mark.parametrize("nv_pad,ne_pad,kw", [
    (8, 64, {}),
    (64, 1024, {"gapped": True}),
    (256, 8192, {"base": 1024, "max_deg": 40}),
    (1024, 32768, {"hubs": 4, "max_deg": 40}),        # widths up to 1024
    (8192, 1 << 17, {"hubs": 3, "gapped": True,
                     "max_deg": 12}),                 # full ladder to 8192
    # Above the floor slab class, the per-graph driver's sized geometry:
    # a gapped label space, degrees up to 40 and one hub of degree 1500
    # (a class of exactly one vertex), so 128 to 1024 and 2048 up are
    # empty ladder classes.
    (2048, 1 << 15, {"sized": True, "gapped": True, "hubs": 1,
                     "hub_deg": 1500, "max_deg": 40}),
], ids=["tiny", "gapped", "based", "hubby", "ladder-top", "sized"])
def test_device_plan_matches_host(nv_pad, ne_pad, kw):
    rng = np.random.default_rng(nv_pad + ne_pad)
    kw = dict(kw)
    sized = kw.pop("sized", False)
    base = kw.get("base", 0)
    src, dst, w = _coalesced_slab(rng, nv_pad, ne_pad, **kw)
    assert rebin_eligible(nv_pad, ne_pad)
    deg = np.bincount(src[src < nv_pad], minlength=nv_pad)
    geom = (sized_geometry(deg, nv_pad) if sized
            else rebin_geometry(nv_pad, ne_pad))
    plan = BucketPlan.build(src, dst, w, nv_local=nv_pad, base=base)
    assert not plan.has_heavy
    bks, heavy, self_loop, perm = jax.device_get(device_rebin_plan(
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w),
        nv_pad=nv_pad, base=base, geometry=geom))

    if sized:
        # The sized plan IS the host plan: same classes, and every
        # bucket equal in shape and content, as is the assembly perm.
        assert [(b.width, len(b.verts)) for b in plan.buckets] \
            == list(geom)
        assert len(geom) < len(rebin_geometry(nv_pad, ne_pad))
        for hb, (verts, dmat, wmat) in zip(plan.buckets, bks):
            assert np.array_equal(verts, np.asarray(hb.verts))
            assert np.array_equal(dmat, np.asarray(hb.dst))
            assert wmat.shape == hb.w.shape
            assert np.array_equal(wmat, np.asarray(hb.w, wmat.dtype))
        assert np.array_equal(perm, build_assemble_perm(
            [b.verts for b in plan.buckets], nv_pad))

    host = {b.width: b for b in plan.buckets}
    for (width, rows), (verts, dmat, wmat) in zip(geom, bks):
        hb = host.get(width)
        n = 0 if hb is None else int((np.asarray(hb.verts) < nv_pad).sum())
        if n:
            # The host bucket embeds as the device bucket's prefix:
            # same ascending-id row order, same gather content, same
            # own-id/zero column padding.
            assert np.array_equal(verts[:n], np.asarray(hb.verts)[:n])
            assert np.array_equal(dmat[:n], np.asarray(hb.dst)[:n])
            assert np.array_equal(wmat[:n], np.asarray(hb.w)[:n])
        assert rows >= n
        assert (verts[n:] == nv_pad).all()
        assert (wmat[n:] == 0).all()
        assert (dmat[n:] == 0).all()
    # Every host bucket width is a kept geometry width (truncated
    # ladder covers the class).
    assert set(host) <= {wd for wd, _ in geom}
    assert np.array_equal(self_loop,
                          np.asarray(plan.self_loop, self_loop.dtype))
    assert (np.asarray(heavy[0]) == nv_pad).all()  # static empty residual

    # Assemble-perm consistency: deg>0 vertices point at their own row
    # in the concatenated bucket space, deg==0 at the trailing default.
    total = sum(r for _, r in geom)
    allverts = np.concatenate([np.asarray(b[0]) for b in bks])
    assert (perm[deg == 0] == total).all()
    touched = np.flatnonzero(deg > 0)
    assert np.array_equal(allverts[perm[touched]], touched)


def test_rebin_geometry_static_and_truncated():
    """Geometry is class-derived only: ladder truncates once a width
    covers nv_pad, rows are pow2 occupancy ceilings, and the SAME class
    always yields the SAME tuple (the compile-key contract)."""
    geom = rebin_geometry(16, 64)
    assert [wd for wd, _ in geom] == [8, 16]
    for wd, rows in geom:
        assert rows & (rows - 1) == 0
    assert geom == rebin_geometry(16, 64)
    widths = [wd for wd, _ in rebin_geometry(4096, 16384)]
    assert widths == [wd for wd in DEFAULT_BUCKETS if wd <= 4096]


def test_sized_geometry_rows_are_host_nb_pad():
    """The sized geometry keeps only the classes that hold a vertex, and
    gives each the host builder's nb_pad rows (pow2 ceiling of the
    count, 1 for a single vertex); its last class covers the maximum
    degree.  A degree past the ladder top, or more degrees than the
    label space holds, is refused."""
    deg = np.array([0, 3, 8, 9, 16, 17, 0, 100, 5, 2, 12, 14, 15],
                   np.int64)
    geom = sized_geometry(deg, 16)
    # w8: 3, 8, 5, 2 -> 4 rows; w16: 9, 16, 12, 14, 15 -> 8 rows;
    # w32: 17 -> 1 row; w64 empty, dropped; w128: 100 -> 1 row.
    assert geom == ((8, 4), (16, 8), (32, 1), (128, 1))
    assert geom[-1][0] >= deg.max()
    assert sized_geometry(np.zeros(16, np.int64), 16) == ()

    rng = np.random.default_rng(11)
    nv_pad = 1024
    src, dst, w = _coalesced_slab(rng, nv_pad, 1 << 16, gapped=True,
                                  hubs=2, hub_deg=600, max_deg=30)
    deg = np.bincount(src[src < nv_pad], minlength=nv_pad)
    plan = BucketPlan.build(src, dst, w, nv_local=nv_pad, base=0)
    geom = sized_geometry(deg, nv_pad)
    assert geom == tuple((b.width, len(b.verts)) for b in plan.buckets)
    assert geom[-1][0] >= deg.max() > geom[-2][0]

    with pytest.raises(ValueError, match="ladder top"):
        sized_geometry(np.array([DEFAULT_BUCKETS[-1] + 1]), 16)
    with pytest.raises(ValueError, match="nv_pad"):
        sized_geometry(np.ones(17, np.int64), 16)


def test_rebin_eligibility_bounds(monkeypatch):
    """Past the ladder top a heavy residual could exist (host oracle
    path); past the element budget the plan is too big.  The env knob
    is read per call."""
    assert rebin_eligible(1024, 16384)
    assert not rebin_eligible(DEFAULT_BUCKETS[-1] * 2, 1 << 16)
    monkeypatch.setenv("CUVITE_REBIN_MAX_ELEMS", "1024")
    assert not rebin_eligible(1024, 16384)


# ---------------------------------------------------------------------------
# Driver integration


@pytest.fixture(scope="module")
def rmat10():
    g = generate_rmat(10, edge_factor=8, seed=3)
    assert g.num_vertices <= 4096 and g.num_edges <= 16384
    return g


def test_full_runs_identical_rebin_on_off(rmat10, monkeypatch):
    """Device re-binning never changes results: bucketed runs with the
    re-binner on (default) and pinned off produce identical labels, Q
    and iteration counts.  (The sort-engine arm rides the slow
    sibling, test_full_runs_identical_rebin_vs_sort.)"""
    monkeypatch.delenv("CUVITE_DEVICE_REBIN", raising=False)
    r_on = louvain_phases(rmat10, engine="bucketed")
    monkeypatch.setenv("CUVITE_DEVICE_REBIN", "0")
    r_off = louvain_phases(rmat10, engine="bucketed")
    assert len(r_on.phases) == len(r_off.phases) >= 3
    assert r_on.total_iterations == r_off.total_iterations
    assert r_on.modularity == r_off.modularity
    assert np.array_equal(r_on.communities, r_off.communities)


@pytest.mark.slow
def test_full_runs_identical_rebin_vs_sort(rmat10, monkeypatch):
    """The cross-engine arm of the on/off identity: the re-binned
    bucketed run also matches the sort engine's labels."""
    monkeypatch.delenv("CUVITE_DEVICE_REBIN", raising=False)
    r_on = louvain_phases(rmat10, engine="bucketed")
    r_sort = louvain_phases(rmat10, engine="sort")
    assert np.array_equal(r_on.communities, r_sort.communities)
    assert r_on.modularity == r_sort.modularity


def test_no_host_plan_build_after_phase0(rmat10, monkeypatch):
    """The acceptance spy: with device re-binning on, the ONLY host
    BucketPlan.build of a multi-phase bucketed run is phase 0's."""
    calls = []
    orig = BucketPlan.build

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(BucketPlan, "build", staticmethod(spy))
    res = louvain_phases(rmat10, engine="bucketed")
    assert len(res.phases) >= 3
    assert len(calls) == 1, \
        f"{len(calls)} host BucketPlan.build calls (want phase 0 only)"


def test_rebin_zero_fresh_compiles_after_phase1(rmat10, monkeypatch):
    """Static geometry holds the compile-key contract: same pow2 class
    across coarse phases => all compiles in phases 0-1 (phase 1 traces
    the re-binned program), none after."""
    monkeypatch.delenv("CUVITE_DEVICE_REBIN", raising=False)
    from cuvite_tpu.utils.trace import Tracer

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
        res = louvain_phases(rmat10, engine="bucketed", tracer=probe)
    finally:
        jax.config.update("jax_log_compiles", False)
        logger.removeHandler(handler)
    assert len(res.phases) >= 3 and len(probe.marks) >= 3
    fresh_after_phase1 = len(compiles) - probe.marks[2]
    assert fresh_after_phase1 == 0, compiles[probe.marks[2]:][:4]


def test_rebin_adds_no_device_syncs(rmat10, monkeypatch):
    """One sync per phase stays one sync per phase: the re-binned
    coarse phases must not change the run's jax.device_get count."""
    def run_counting():
        calls = []
        orig = jax.device_get

        def spy(x):
            calls.append(1)
            return orig(x)

        monkeypatch.setattr(jax, "device_get", spy)
        try:
            res = louvain_phases(rmat10, engine="bucketed")
        finally:
            monkeypatch.setattr(jax, "device_get", orig)
        return res, len(calls)

    monkeypatch.delenv("CUVITE_DEVICE_REBIN", raising=False)
    r_on, n_on = run_counting()
    monkeypatch.setenv("CUVITE_DEVICE_REBIN", "0")
    r_off, n_off = run_counting()
    assert np.array_equal(r_on.communities, r_off.communities)
    assert n_on == n_off, \
        f"device re-binning changed sync count: {n_on} vs {n_off}"


def test_rebin_device_fraction_in_tracer(rmat10):
    """The bench telemetry counters: every eligible coarse phase of a
    bucketed run re-bins on device when the knob is on."""
    from cuvite_tpu.utils.trace import Tracer

    tr = Tracer(enabled=True)
    res = louvain_phases(rmat10, engine="bucketed", tracer=tr)
    total = tr.counters.get("rebin_phases", 0)
    dev = tr.counters.get("rebin_device_phases", 0)
    assert len(res.phases) >= 3
    # Every coarse-phase runner counts itself (the terminating
    # no-improvement attempt included, so >= recorded phases - 1).
    assert total >= len(res.phases) - 1
    assert dev == total  # the floor class is rebin-eligible


def test_sized_rebin_lfr_identical_and_counted(monkeypatch):
    """Above the floor slab class the driver sizes the device plan from
    the coarse graph's degrees: on a small LFR graph whose phase 1
    (about 200 communities, over 16384 coarse edges) re-bins on the
    device above the floor, labels, Q and every phase's iteration count
    equal the host-plan run, the sized phase is counted, and the plan
    slots fall below the class-static geometry's for the same phases."""
    import cuvite_tpu.louvain.driver as drv
    from cuvite_tpu.core.graph import Graph
    from cuvite_tpu.utils.trace import Tracer
    from cuvite_tpu.workloads.synth import lfr_edges

    nv, s, d, _ = lfr_edges(6000, 2.0, 1.0, 20, 30, 20, 40, 0.3, seed=7)
    g = Graph.from_edges(nv, s, d)
    seen = []  # (nv_pad, ne_class, geometry) per device re-binned phase
    orig = drv.device_rebin_plan

    def spy(src, dst, w, *, nv_pad, base, geometry):
        seen.append((nv_pad, src.shape[0], geometry))
        return orig(src, dst, w, nv_pad=nv_pad, base=base,
                    geometry=geometry)

    monkeypatch.setattr(drv, "device_rebin_plan", spy)
    monkeypatch.delenv("CUVITE_DEVICE_REBIN", raising=False)
    tr = Tracer(enabled=True)
    r_on = louvain_phases(g, engine="bucketed", tracer=tr)
    monkeypatch.setenv("CUVITE_DEVICE_REBIN", "0")
    r_off = louvain_phases(g, engine="bucketed")

    assert np.array_equal(r_on.communities, r_off.communities)
    assert r_on.modularity == r_off.modularity
    assert [p.iterations for p in r_on.phases] \
        == [p.iterations for p in r_off.phases]
    assert any(ne > 16384 for _, ne, _ in seen)
    assert tr.counters["rebin_sized_phases"] \
        == sum(ne > 16384 for _, ne, _ in seen) >= 1
    assert tr.counters["rebin_device_phases"] == len(seen)
    slots = tr.counters["rebin_slots"]
    assert slots == sum(r * wd for _, _, geom in seen for wd, r in geom)
    static = sum(r * wd for nv_pad, ne, _ in seen
                 for wd, r in rebin_geometry(nv_pad, ne))
    assert slots < static
    # The floor class keeps the class-static geometry.
    for nv_pad, ne, geom in seen:
        if ne <= 16384:
            assert geom == rebin_geometry(nv_pad, ne)


# ---------------------------------------------------------------------------
# Batched integration


def test_batched_rebinned_identical_and_spied(monkeypatch):
    """The serving path: a batched bucketed run re-bins its coarse
    phases on device ('rebinned' in phase_engines), produces labels/Q
    bit-identical to the host-plan arm, and makes NO BucketPlan.build
    call after prepare (phase 0).  (The B=1 and per-graph-driver
    cross-checks ride the slow sibling,
    test_batched_rebinned_matches_b1_and_solo.)"""
    from cuvite_tpu.louvain.driver import louvain_many

    gs = [generate_rmat(8, edge_factor=8, seed=s) for s in (1, 2)]
    monkeypatch.delenv("CUVITE_DEVICE_REBIN", raising=False)
    on = louvain_many(gs, engine="bucketed")
    assert on.phase_engines[0] == "bucketed"
    assert all(e == "rebinned" for e in on.phase_engines[1:])
    assert len(on.phase_engines) >= 2

    monkeypatch.setenv("CUVITE_DEVICE_REBIN", "0")
    off = louvain_many(gs, engine="bucketed")
    assert all(e == "fused" for e in off.phase_engines[1:])
    monkeypatch.delenv("CUVITE_DEVICE_REBIN", raising=False)
    for r_on, r_off in zip(on.results, off.results):
        assert r_on.modularity == r_off.modularity
        assert np.array_equal(r_on.communities, r_off.communities)

    # The batched build spy: warm path re-runs prepare (phase 0 builds
    # are legal) but the re-binned EXECUTE phases must build nothing —
    # count builds with the coarse phases forced to fused vs rebinned;
    # the rebinned arm must not add any.
    calls = []
    orig = BucketPlan.build

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(BucketPlan, "build", staticmethod(spy))
    louvain_many(gs, engine="bucketed")
    n_rebinned = len(calls)
    calls.clear()
    monkeypatch.setenv("CUVITE_DEVICE_REBIN", "0")
    louvain_many(gs, engine="bucketed")
    assert n_rebinned <= len(calls)  # host arm builds at least as many
    # and the rebinned arm's builds are all phase-0 (prepare) builds:
    # re-running prepare alone accounts for every one of them.
    monkeypatch.delenv("CUVITE_DEVICE_REBIN", raising=False)
    from cuvite_tpu.core.batch import batch_slabs
    from cuvite_tpu.louvain.batched import prepare_batch

    calls.clear()
    prepare_batch(batch_slabs(gs), engine="bucketed")
    assert len(calls) == n_rebinned


@pytest.mark.slow
def test_batched_rebinned_matches_b1_and_solo(monkeypatch):
    """Cross-arm identity of the re-binned serving path: every tenant
    of a B>1 re-binned batch matches its own B=1 batch AND the
    per-graph bucketed driver bit-for-bit."""
    from cuvite_tpu.louvain.driver import louvain_many

    monkeypatch.delenv("CUVITE_DEVICE_REBIN", raising=False)
    gs = [generate_rmat(8, edge_factor=8, seed=s) for s in (1, 2)]
    on = louvain_many(gs, engine="bucketed")
    assert all(e == "rebinned" for e in on.phase_engines[1:])
    for g, r_on in zip(gs, on.results):
        b1 = louvain_many([g], engine="bucketed")
        solo = louvain_phases(g, engine="bucketed")
        assert np.array_equal(r_on.communities, b1.results[0].communities)
        assert np.array_equal(r_on.communities, solo.communities)


def test_batched_second_batch_zero_fresh_compiles(monkeypatch):
    """Serving amortization with device re-binning ON: a second batch
    of different same-class graphs compiles nothing — including the
    re-binned coarse phases."""
    from cuvite_tpu.core.batch import bucket_shape_for
    from cuvite_tpu.louvain.driver import louvain_many
    from cuvite_tpu.obs import CompileWatcher

    monkeypatch.delenv("CUVITE_DEVICE_REBIN", raising=False)
    gs = [generate_rmat(8, edge_factor=8, seed=s) for s in (5, 6)]
    fresh = [generate_rmat(8, edge_factor=8, seed=s) for s in (7, 8)]
    shape = bucket_shape_for(gs + fresh)
    louvain_many(gs, engine="bucketed", bucket_shape=shape)  # warm
    with CompileWatcher() as watch:
        br = louvain_many(fresh, engine="bucketed", bucket_shape=shape)
    assert watch.compiles == [], \
        f"second same-class batch recompiled: {watch.compiles}"
    assert all(e == "rebinned" for e in br.phase_engines[1:])


# ---------------------------------------------------------------------------
# The coalesce at the packing boundary vs the float64 oracle


def _chokepoint_slab(nv_pad, ne_pad, seed):
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


def _oracle(src, ckey, w, nv_pad):
    """Sorted-unique real (src, ckey) pairs, weights summed in float64
    (dyadic inputs: every f32 partial sum is exact, so engines must
    match BIT-for-bit after the cast)."""
    real = src < nv_pad
    keys = src[real].astype(np.int64) * (nv_pad + 1) + ckey[real]
    order = np.argsort(keys, kind="stable")
    ks, ws = keys[order], w[real][order].astype(np.float64)
    uniq, start = np.unique(ks, return_index=True)
    sums = np.add.reduceat(ws, start) if len(ws) else ws
    return (uniq // (nv_pad + 1)).astype(src.dtype), \
        (uniq % (nv_pad + 1)).astype(ckey.dtype), \
        sums.astype(w.dtype)


def _assert_matches_oracle(out, src, dst, w, nv_pad):
    s_ref, c_ref, w_ref = _oracle(src, dst, w, nv_pad)
    src_c, ckey_c, w_c, n = (np.asarray(x) for x in jax.device_get(out))
    n = int(n)
    assert n == len(s_ref)
    assert np.array_equal(src_c[:n], s_ref)
    assert np.array_equal(ckey_c[:n], c_ref)
    assert np.array_equal(w_c[:n], w_ref)
    assert (src_c[n:] == nv_pad).all()


@pytest.mark.parametrize("nv_pad", [1 << 15, 1 << 16],
                         ids=["widest-legal-pack", "first-ineligible"])
def test_bigclass_engines_match_oracle(nv_pad):
    """The coalesce at the packing boundary: nv_pad = 2^15 is the
    widest legal 31-bit pack, 2^16 the first class past it (the sort
    degrades to the variadic comparator)."""
    ne_pad = 8192
    src, dst, w = _chokepoint_slab(nv_pad, ne_pad, seed=nv_pad)
    arrs = tuple(jnp.asarray(x) for x in (src, dst, w))
    out = coalesced_runs(*arrs, nv_pad=nv_pad)
    _assert_matches_oracle(out, src, dst, w, nv_pad)
