"""The bucketed engine must be step-for-step identical to the sort engine
(and therefore to the reference-semantics oracle)."""

import functools

import numpy as np
import pytest

from cuvite_tpu.core.distgraph import DistGraph
from cuvite_tpu.core.graph import Graph
from cuvite_tpu.evaluate.modularity import modularity as mod_oracle
from cuvite_tpu.io.generate import generate_rgg, generate_rmat
from cuvite_tpu.louvain.bucketed import BucketPlan
from cuvite_tpu.louvain.driver import PhaseRunner, louvain_phases


def _run_engines_one_phase(graph, iters=4):
    outs = []
    for engine in ("sort", "bucketed"):
        dg = DistGraph.build(graph, 1)
        r = PhaseRunner(dg, engine=engine)
        comm = r.comm0
        trace = []
        for _ in range(iters):
            target, q, moved, _ = r._step(r.src, r.dst, r.w, comm, r.vdeg,
                                          r.constant)
            trace.append((np.asarray(target), float(q), int(moved)))
            comm = target
        outs.append(trace)
    return outs


@pytest.mark.parametrize("maker", [
    lambda: generate_rgg(256, seed=1),
    lambda: generate_rmat(9, edge_factor=8, seed=2),   # has heavy vertices
])
def test_engines_identical_trajectories(maker):
    graph = maker()
    sort_trace, bucket_trace = _run_engines_one_phase(graph)
    for it, ((t1, q1, m1), (t2, q2, m2)) in enumerate(
            zip(sort_trace, bucket_trace)):
        np.testing.assert_array_equal(
            t1, t2, err_msg=f"engines diverge at iteration {it}")
        assert q2 == pytest.approx(q1, abs=1e-5)
        assert m1 == m2


def test_engines_identical_on_karate(karate):
    sort_trace, bucket_trace = _run_engines_one_phase(karate, iters=5)
    for (t1, q1, _), (t2, q2, _) in zip(sort_trace, bucket_trace):
        np.testing.assert_array_equal(t1, t2)


def test_bucket_plan_partitions_all_edges():
    g = generate_rmat(9, edge_factor=8, seed=2)
    dg = DistGraph.build(g, 1)
    sh = dg.shards[0]
    plan = BucketPlan.build(np.asarray(sh.src), np.asarray(sh.dst),
                            np.asarray(sh.w), nv_local=dg.nv_pad, base=0)
    # every real edge is represented exactly once: bucket row weights +
    # heavy weights sum to the total
    total = sum(float(b.w.sum()) for b in plan.buckets) \
        + float(plan.heavy_w.sum())
    assert total == pytest.approx(float(np.asarray(sh.w).sum()), rel=1e-6)
    # vertex coverage: every real vertex with degree > 0 appears in exactly
    # one bucket or the heavy set
    deg = np.bincount(np.asarray(sh.src)[np.asarray(sh.src) < dg.nv_pad],
                      minlength=dg.nv_pad)
    in_bucket = np.zeros(dg.nv_pad, dtype=int)
    for b in plan.buckets:
        real = b.verts[b.verts < dg.nv_pad]
        in_bucket[real] += 1
    heavy_real = np.unique(
        np.asarray(plan.heavy_src)[np.asarray(plan.heavy_src) < dg.nv_pad])
    in_bucket[heavy_real] += 1
    assert np.all(in_bucket[deg > 0] == 1)
    assert np.all(in_bucket[deg == 0] == 0)


def test_full_run_bucketed_matches_sort(karate):
    r1 = louvain_phases(karate, engine="sort")
    r2 = louvain_phases(karate, engine="bucketed")
    np.testing.assert_array_equal(r1.communities, r2.communities)
    assert r2.modularity == pytest.approx(r1.modularity, abs=1e-5)


def test_bucketed_weighted_selfloops():
    g = Graph.from_edges(6, [0, 1, 2, 3, 0, 4], [1, 2, 0, 3, 0, 5],
                         weights=[2.0, 1.0, 3.0, 5.0, 4.0, 1.0])
    sort_trace, bucket_trace = _run_engines_one_phase(g, iters=3)
    for (t1, q1, _), (t2, q2, _) in zip(sort_trace, bucket_trace):
        np.testing.assert_array_equal(t1, t2)
        assert q2 == pytest.approx(q1, abs=1e-6)


def test_heavy_path_and_chunking_with_small_widths():
    """Exercise the heavy fallback and lax.map chunked rows explicitly by
    shrinking the bucket widths (default widths leave rmat(9) heavy-free)."""
    import jax.numpy as jnp
    import cuvite_tpu.louvain.bucketed as bk
    from cuvite_tpu.louvain.bucketed import BucketPlan, bucketed_step
    from cuvite_tpu.louvain.step import make_single_step

    g = generate_rmat(9, edge_factor=8, seed=2)
    dg = DistGraph.build(g, 1)
    sh = dg.shards[0]
    plan = BucketPlan.build(np.asarray(sh.src), np.asarray(sh.dst),
                            np.asarray(sh.w), nv_local=dg.nv_pad, base=0,
                            widths=(4, 8))  # most vertices become heavy
    assert plan.has_heavy
    vdt, wdt = np.int32, np.float32
    buckets = tuple(
        (jnp.asarray(b.verts.astype(vdt)), jnp.asarray(b.dst.astype(vdt)),
         jnp.asarray(b.w.astype(wdt))) for b in plan.buckets)
    heavy = (jnp.asarray(plan.heavy_src.astype(vdt)),
             jnp.asarray(plan.heavy_dst.astype(vdt)),
             jnp.asarray(plan.heavy_w.astype(wdt)))
    sl = jnp.asarray(plan.self_loop.astype(wdt))
    nvt = dg.total_padded_vertices
    comm = jnp.arange(nvt, dtype=vdt)
    vdeg = jnp.asarray(dg.padded_weighted_degrees().astype(wdt))
    const = jnp.asarray(1.0 / g.total_edge_weight_twice(), dtype=wdt)

    ref_step = make_single_step(nvt)
    src, dst, w = dg.stacked_edges()
    for it in range(3):
        t1, q1, m1, _ = ref_step(jnp.asarray(src), jnp.asarray(dst),
                              jnp.asarray(w), comm, vdeg, const)
        t2, q2, m2, _ = bucketed_step(buckets, heavy, sl, comm, vdeg, const,
                                   nv_total=nvt, sentinel=np.iinfo(vdt).max)
        np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2),
                                      err_msg=f"iter {it}")
        assert float(q2) == pytest.approx(float(q1), abs=1e-5)
        comm = t1

    # chunked path: force a tiny chunk so lax.map runs with many chunks
    old = bk.ROW_ELEMS_CHUNK
    try:
        bk.ROW_ELEMS_CHUNK = 1 << 10
        plan2 = BucketPlan.build(np.asarray(sh.src), np.asarray(sh.dst),
                                 np.asarray(sh.w), nv_local=dg.nv_pad,
                                 base=0, widths=(4, 64, 256))
        buckets2 = tuple(
            (jnp.asarray(b.verts.astype(vdt)),
             jnp.asarray(b.dst.astype(vdt)),
             jnp.asarray(b.w.astype(wdt))) for b in plan2.buckets)
        heavy2 = (jnp.asarray(plan2.heavy_src.astype(vdt)),
                  jnp.asarray(plan2.heavy_dst.astype(vdt)),
                  jnp.asarray(plan2.heavy_w.astype(wdt)))
        comm = jnp.arange(nvt, dtype=vdt)
        t3, q3, _, _ = bucketed_step(buckets2, heavy2, sl, comm, vdeg, const,
                                  nv_total=nvt, sentinel=np.iinfo(vdt).max)
        t0, q0, _, _ = ref_step(jnp.asarray(src), jnp.asarray(dst),
                             jnp.asarray(w), comm, vdeg, const)
        np.testing.assert_array_equal(np.asarray(t0), np.asarray(t3))
    finally:
        bk.ROW_ELEMS_CHUNK = old


@pytest.mark.parametrize("nshards", [2, 8])
def test_multishard_bucketed_matches_single(nshards):
    """The sharded bucketed step (shard_map + all_gather/psum) must produce
    the same trajectory as the single-shard engines."""
    g = generate_rmat(9, edge_factor=8, seed=2)
    single = _run_engines_one_phase(g)[1]

    from cuvite_tpu.comm.mesh import make_mesh

    dg1 = DistGraph.build(g, 1)
    dg = DistGraph.build(g, nshards)
    mesh = make_mesh(nshards)
    r = PhaseRunner(dg, mesh=mesh, engine="bucketed")
    comm = r.comm0
    for it, (t1, q1, m1) in enumerate(single):
        target, q, moved, ovf = r._step(None, None, None, comm, r.vdeg,
                                        r.constant)
        assert not bool(ovf), "sparse budget overflow in test"
        # Labels are padded-space vertex ids and the padded layouts differ
        # per nshards: map each to original-id space, compare as partitions.
        lab1 = dg1.pad_to_old[t1[dg1.old_to_pad]]
        labN = dg.pad_to_old[np.asarray(target)[dg.old_to_pad]]
        assert _partition_signature(lab1) == _partition_signature(labN), \
            f"diverged at iteration {it}"
        assert float(q) == pytest.approx(q1, abs=1e-5)
        assert int(moved) == m1
        comm = target


def _partition_signature(labels):
    """Canonical form of a partition: tuple of frozensets of members."""
    import collections

    groups = collections.defaultdict(list)
    for v, c in enumerate(np.asarray(labels)):
        groups[int(c)].append(v)
    return frozenset(frozenset(m) for m in groups.values())


@pytest.mark.parametrize("nshards", [4])
def test_full_run_multishard_bucketed(karate, nshards):
    r1 = louvain_phases(karate, engine="bucketed")
    rN = louvain_phases(karate, nshards=nshards, engine="bucketed")
    assert rN.modularity == pytest.approx(r1.modularity, abs=1e-4)
    np.testing.assert_array_equal(
        _np_canon(r1.communities), _np_canon(rN.communities))


def _np_canon(labels):
    """Renumber labels by first appearance so partitions compare equal."""
    labels = np.asarray(labels)
    _, first = np.unique(labels, return_index=True)
    order = np.argsort(first)
    remap = np.empty(len(order), dtype=np.int64)
    remap[order] = np.arange(len(order))
    return remap[np.searchsorted(np.unique(labels), labels)]


def test_zero_weight_edges_engines_agree():
    """Zero-weight real edges must be candidates in both engines."""
    rng = np.random.default_rng(7)
    g0 = generate_rgg(128, seed=1)
    w = np.asarray(g0.weights).copy()
    # zero out ~20% of undirected edges symmetrically: rebuild from edges
    src, dst = g0.sources(), g0.tails
    keep_mask = src < dst
    es, ed = src[keep_mask], dst[keep_mask]
    ew = w[keep_mask]
    ew[rng.random(len(ew)) < 0.2] = 0.0
    g = Graph.from_edges(128, es, ed, weights=ew)
    sort_trace, bucket_trace = _run_engines_one_phase(g, iters=4)
    for it, ((t1, q1, m1), (t2, q2, m2)) in enumerate(
            zip(sort_trace, bucket_trace)):
        np.testing.assert_array_equal(t1, t2, err_msg=f"iter {it}")
        assert m1 == m2


def test_build_assemble_perm_properties():
    """Direct pin of the scatter-free assembly map: bucket vertices map to
    their own row in the concatenated space, everyone else (heavy /
    degree-0 / padding) to the trailing default slot."""
    from cuvite_tpu.louvain.bucketed import build_assemble_perm

    nv = 10
    verts_a = np.array([3, 7, nv, nv])     # padded bucket: rows 0..3
    verts_b = np.array([1, 2, 5])          # second bucket: rows 4..6
    perm = build_assemble_perm([verts_a, verts_b], nv)
    total = len(verts_a) + len(verts_b)
    assert perm.dtype == np.int32 and perm.shape == (nv,)
    assert perm[3] == 0 and perm[7] == 1          # bucket a rows
    assert perm[1] == 4 and perm[2] == 5 and perm[5] == 6
    # not in any bucket -> default slot
    for v in (0, 4, 6, 8, 9):
        assert perm[v] == total, (v, perm[v])


def _row_argmax_packed(cmat, wmat, aymat, smat, curr_comm, vdeg_v, sl_v,
                       ax_v, constant, sentinel, id_bound):
    """The earlier sorted dedup, kept as the parity oracle: one packed
    int32 key ``(c << bits) | slot``, the payloads and the suffix sum at
    the next leader read back by per-row ``take_along_axis``."""
    import jax
    import jax.numpy as jnp
    from cuvite_tpu.louvain.bucketed import RowResult

    wdt = wmat.dtype
    D = cmat.shape[1]
    counter0 = jnp.sum(
        jnp.where(cmat == curr_comm[:, None], wmat, 0.0), axis=1
    ).astype(wdt)
    eix_v = counter0 - sl_v
    bits = (D - 1).bit_length()
    assert (int(id_bound) << bits) <= (1 << 31)
    iota = jax.lax.broadcasted_iota(jnp.int32, cmat.shape, 1)
    k_s = jax.lax.sort((cmat << bits) | iota, dimension=1)
    slot = k_s & ((1 << bits) - 1)
    c_s = k_s >> bits
    w_s = jnp.take_along_axis(wmat, slot, axis=1)
    ay_s = jnp.take_along_axis(aymat, slot, axis=1)
    s_s = (jnp.take_along_axis(smat, slot, axis=1)
           if smat is not None else None)
    leader = jnp.concatenate(
        [jnp.ones_like(c_s[:, :1], dtype=bool), c_s[:, 1:] != c_s[:, :-1]],
        axis=1)
    pos = jax.lax.broadcasted_iota(jnp.int32, c_s.shape, 1)
    nxt = jnp.flip(jax.lax.cummin(
        jnp.flip(jnp.where(leader, pos, D), 1), axis=1), 1)
    nxt = jnp.concatenate([nxt[:, 1:], jnp.full_like(nxt[:, :1], D)], axis=1)
    suf = jnp.flip(jnp.cumsum(jnp.flip(w_s, 1), axis=1), 1)
    suf_ext = jnp.concatenate([suf, jnp.zeros_like(suf[:, :1])], axis=1)
    run_sum = suf - jnp.take_along_axis(suf_ext, nxt, axis=1)
    valid = leader & (c_s != curr_comm[:, None])
    gain = 2.0 * (run_sum - eix_v[:, None]) \
        - 2.0 * vdeg_v[:, None] * (ay_s - ax_v[:, None]) * constant
    gain = jnp.where(valid, gain, jnp.array(-jnp.inf, dtype=wdt))
    best_gain = jnp.max(gain, axis=1)
    at_best = valid & (gain == best_gain[:, None])
    best_c = jnp.min(jnp.where(at_best, c_s, sentinel), axis=1)
    best_size = None
    if smat is not None:
        best_size = jnp.min(
            jnp.where(c_s == best_c[:, None], s_s, sentinel), axis=1)
    return RowResult(best_c=best_c, best_gain=best_gain, counter0=counter0,
                     best_size=best_size)


_NV_ROWS = 1000   # community ids of the parity rows


def _dedup_rows(width, weights, sparse, seed=0):
    """Rows for the dedup parity: repeated communities (runs), the row's own
    community (and rows made only of it: no candidate), zero-weight slots,
    and a three-valued degree table so candidate gains tie.  Weights are
    uint8 {0, 1} widened as the step widens them, or quarter-valued floats:
    every sum of them is exact in float32, so the all-pairs einsum and the
    suffix-sum difference agree bit for bit.  ``vdeg_v``/``ax_v`` are small
    dyadics so the gain's product rounds nowhere."""
    rng = np.random.default_rng(seed + width)
    rows = 3 if width > 1024 else 9
    own = rng.integers(0, _NV_ROWS, size=rows).astype(np.int32)
    pool = rng.integers(0, _NV_ROWS, size=(rows, 6)).astype(np.int32)
    pick = rng.integers(0, pool.shape[1], size=(rows, width))
    cmat = np.take_along_axis(pool, pick, axis=1)
    fresh = rng.random((rows, width)) < 0.4
    cmat = np.where(fresh, rng.integers(0, _NV_ROWS, (rows, width)), cmat)
    cmat = np.where(rng.random((rows, width)) < 0.15, own[:, None], cmat)
    cmat[0] = own[0]
    cmat = cmat.astype(np.int32)
    if weights == "unit":
        w = (rng.random((rows, width)) < 0.8).astype(np.uint8)
    else:
        w = rng.choice(np.float32([0.0, 0.25, 0.5, 1.5, 2.0, 3.75]),
                       size=(rows, width))
    deg_of = rng.choice(np.float32([1.0, 2.0, 3.0]), size=_NV_ROWS)
    size_of = rng.integers(1, 4, size=_NV_ROWS).astype(np.int32)
    return dict(
        cmat=cmat, wmat=w.astype(np.float32), aymat=deg_of[cmat],
        smat=size_of[cmat] if sparse else None, curr_comm=own,
        vdeg_v=rng.choice(np.float32([0.5, 1.0, 2.25]), size=rows),
        sl_v=rng.choice(np.float32([0.0, 0.25]), size=rows),
        ax_v=rng.choice(np.float32([0.0, 1.0, 2.0]), size=rows),
        constant=np.float32(2.0 ** -6), sentinel=np.iinfo(np.int32).max)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("sparse", [False, True], ids=["replicated", "sparse"])
@pytest.mark.parametrize("weights", ["unit", "float"])
@pytest.mark.parametrize("width", [64, 128, 384, 4096])
def test_sorted_dedup_matches_all_pairs_and_packed(width, weights, sparse):
    """The sorted dedup (payloads as sort operands, running-max run sums)
    equals the all-pairs dedup and the earlier packed-key formulation bit
    for bit in every output."""
    import jax
    import jax.numpy as jnp
    from cuvite_tpu.louvain.bucketed import _row_argmax, _row_argmax_sorted

    a = _dedup_rows(width, weights, sparse)
    arrays = {k: (None if v is None else jnp.asarray(v))
              for k, v in a.items() if k not in ("constant", "sentinel")}
    order = ("cmat", "wmat", "aymat", "smat", "curr_comm", "vdeg_v", "sl_v",
             "ax_v")

    def call(fn, **kw):
        return jax.jit(lambda *xs: fn(*xs, a["constant"], a["sentinel"],
                                      **kw))(*(arrays[k] for k in order))

    got = call(_row_argmax_sorted)
    for name, want in (
            ("all-pairs", call(_row_argmax)),
            ("packed", call(_row_argmax_packed, id_bound=_NV_ROWS))):
        for field in ("best_c", "best_gain", "counter0", "best_size"):
            g, w = getattr(got, field), getattr(want, field)
            if not sparse and field == "best_size":
                assert g is None and w is None
                continue
            np.testing.assert_array_equal(
                _bits(g), _bits(w), err_msg=f"{field} differs from {name}")
    # The rows exercise what they claim: a row with no candidate, and ties.
    assert int(got.best_c[0]) == a["sentinel"]
    assert np.isneginf(float(got.best_gain[0]))


def _gather_operand_ranks(fn, args):
    import jax
    from cuvite_tpu.analysis.widthaudit import _walk_eqns

    # A fresh wrapper per trace: make_jaxpr caches by function object.
    jaxpr = jax.make_jaxpr(lambda *xs: fn(*xs))(*args)
    return [len(e.invars[0].aval.shape) for e in _walk_eqns(jaxpr)
            if e.primitive.name == "gather"]


@pytest.mark.parametrize("sparse", [False, True], ids=["replicated", "sparse"])
def test_sorted_dedup_gathers_only_tables(sparse):
    """Traced through the step's chunk dispatch, the sorted dedup gathers
    from the 1-D community tables only: no gather reads a [rows, D] row
    matrix.  The packed oracle, traced the same way, does (the guard
    sees row gathers)."""
    import jax.numpy as jnp
    import cuvite_tpu.louvain.bucketed as bk

    rows, width, nv = 64, 256, 4096
    comm = jnp.arange(nv, dtype=jnp.int32)
    cdeg = jnp.ones((nv,), jnp.float32)
    csize = jnp.ones((nv,), jnp.int32)

    def step(w, dst, curr, vdeg_v, sl_v, ax_v):
        return bk._rows_chunked(
            w, dst, curr, vdeg_v, sl_v, ax_v, jnp.float32(1.0),
            np.iinfo(np.int32).max, lambda dm: jnp.take(comm, dm),
            lambda dm, cm: jnp.take(cdeg, cm),
            (lambda dm, cm: jnp.take(csize, dm)) if sparse else
            (lambda dm, cm: None), jnp.float32)

    args = (jnp.ones((rows, width), jnp.uint8),
            jnp.zeros((rows, width), jnp.int32),
            jnp.zeros((rows,), jnp.int32), jnp.ones((rows,), jnp.float32),
            jnp.zeros((rows,), jnp.float32), jnp.zeros((rows,), jnp.float32))
    ranks = _gather_operand_ranks(step, args)
    assert ranks and set(ranks) == {1}, ranks
    assert len(ranks) == (3 if sparse else 2)

    sorted_fn = bk._row_argmax_sorted
    try:
        bk._row_argmax_sorted = functools.partial(_row_argmax_packed,
                                                  id_bound=nv)
        assert 2 in _gather_operand_ranks(step, args)
    finally:
        bk._row_argmax_sorted = sorted_fn
