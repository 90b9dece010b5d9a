"""Pallas kernel vs XLA-fallback parity (interpret mode on CPU)."""

import numpy as np
import pytest

import jax.numpy as jnp

from cuvite_tpu.kernels.row_argmax import row_argmax_pallas
from cuvite_tpu.louvain.bucketed import _row_argmax

SENTINEL = np.iinfo(np.int32).max


def _bucket_case(n_rows, width, nv, seed):
    rng = np.random.default_rng(seed)
    cmat = rng.integers(0, nv, size=(n_rows, width)).astype(np.int32)
    # Multiples of 1/16: float sums are exact in any order, so the kernel
    # and the XLA path must agree bit-for-bit.
    wmat = (rng.integers(1, 32, size=(n_rows, width)) / 16.0).astype(
        np.float32)
    curr = rng.integers(0, nv, size=n_rows).astype(np.int32)
    # Some rows keep slots in the current community (the is_cc mask path).
    cmat[: n_rows // 2, 0] = curr[: n_rows // 2]
    vdeg = (rng.integers(1, 64, size=n_rows) / 4.0).astype(np.float32)
    # Self-loop weight <= the row's weight into its current community.
    sl = np.where(cmat[:, 0] == curr, wmat[:, 0] / 2.0, 0.0).astype(
        np.float32)
    comm_deg = (rng.integers(1, 256, size=nv) / 8.0).astype(np.float32)
    constant = np.float32(1.0 / 64.0)
    return cmat, wmat, curr, vdeg, sl, comm_deg, constant


@pytest.mark.parametrize("width", [8, 32, 64, 256])
@pytest.mark.parametrize("seed", [0, 3])
@pytest.mark.parametrize("constant", [None, np.float32(0.3)])
def test_row_argmax_pallas_matches_xla(width, seed, constant):
    """Widths 8/32 exercise the unrolled candidate loop; 64/256 the
    fori_loop form added for the wide classes (VERDICT r3 item 4).
    constant=0.3 (non-dyadic) pins the gain's operand ASSOCIATION to the
    XLA path's — with the default dyadic 1/64 every association is exact
    and a reassociation regression would be invisible."""
    n_rows, nv = 256, 500
    cmat, wmat, curr, vdeg, sl, comm_deg, _const_dyadic = _bucket_case(
        n_rows, width, nv, seed)
    constant = _const_dyadic if constant is None else constant

    # Reference path mirrors bucketed_step: both kernels take the self-loop
    # weight and derive eix = counter0 - sl row-locally.
    is_cc = cmat == curr[:, None]
    counter0 = np.sum(np.where(is_cc, wmat, 0.0), axis=1).astype(np.float32)
    ay = comm_deg[cmat]                     # pre-gathered outside the kernel
    ax = comm_deg[curr] - vdeg
    ref = _row_argmax(
        jnp.asarray(cmat), jnp.asarray(wmat), jnp.asarray(ay), None,
        jnp.asarray(curr), jnp.asarray(vdeg), jnp.asarray(sl),
        jnp.asarray(ax), jnp.asarray(constant), SENTINEL,
    )
    bc, bg, c0 = row_argmax_pallas(
        jnp.asarray(np.ascontiguousarray(cmat.T)),
        jnp.asarray(np.ascontiguousarray(wmat.T)),
        jnp.asarray(np.ascontiguousarray(ay.T)),
        jnp.asarray(curr), jnp.asarray(vdeg), jnp.asarray(sl),
        jnp.asarray(ax), jnp.asarray(constant),
        sentinel=SENTINEL, tile_n=128, interpret=True,
    )
    assert np.array_equal(np.asarray(c0), counter0)
    assert np.array_equal(np.asarray(bg), np.asarray(ref.best_gain))
    assert np.array_equal(np.asarray(bc), np.asarray(ref.best_c))


def test_row_argmax_pallas_no_candidates():
    """Rows whose every slot sits in the current community -> sentinel."""
    n_rows, width, nv = 128, 8, 50
    rng = np.random.default_rng(1)
    curr = rng.integers(0, nv, size=n_rows).astype(np.int32)
    cmat = np.repeat(curr[:, None], width, axis=1)
    wmat = np.ones((n_rows, width), dtype=np.float32)
    vdeg = np.ones(n_rows, dtype=np.float32)
    sl = np.zeros(n_rows, dtype=np.float32)
    comm_deg = np.ones(nv, dtype=np.float32)
    ay = comm_deg[cmat]
    ax = comm_deg[curr] - vdeg
    bc, bg, c0 = row_argmax_pallas(
        jnp.asarray(np.ascontiguousarray(cmat.T)),
        jnp.asarray(np.ascontiguousarray(wmat.T)),
        jnp.asarray(np.ascontiguousarray(ay.T)),
        jnp.asarray(curr), jnp.asarray(vdeg), jnp.asarray(sl),
        jnp.asarray(ax), jnp.asarray(np.float32(0.01)),
        sentinel=SENTINEL, tile_n=128, interpret=True,
    )
    assert np.all(np.asarray(bc) == SENTINEL)
    assert np.all(np.isneginf(np.asarray(bg)))
    assert np.allclose(np.asarray(c0), width)


# ---------------------------------------------------------------------------
# The heavy class (degree > widths[-1]): bucketed_step's sorted residual,
# fed rows the quadratic all-pairs path scores too.  Each row r is vertex
# r with every slot in the heavy residual and no degree class, so the
# step's targets and Q come from the sorted heavy path alone.


def _heavy_step(dst, wmat, comm, vdeg, sl, constant):
    """bucketed_step over a heavy residual holding row r's slots as
    vertex r's edges (padding src == nv, w == 0)."""
    import functools

    import jax

    from cuvite_tpu.louvain.bucketed import bucketed_step

    n_rows, width = dst.shape
    nv = comm.shape[0]
    n = n_rows * width
    npad = 1 << max(n - 1, 1).bit_length()
    hs = np.full(npad, nv, np.int32)
    hd = np.zeros(npad, np.int32)
    hw = np.zeros(npad, np.float32)
    hs[:n] = np.repeat(np.arange(n_rows, dtype=np.int32), width)
    hd[:n] = dst.ravel()
    hw[:n] = wmat.ravel()
    step = jax.jit(functools.partial(bucketed_step, nv_total=nv,
                                     sentinel=SENTINEL))
    t, q, _n, _ovf = step((), tuple(jnp.asarray(x) for x in (hs, hd, hw)),
                          jnp.asarray(sl), jnp.asarray(comm),
                          jnp.asarray(vdeg), jnp.asarray(constant))
    return np.asarray(t), float(q)


def _heavy_oracle(dst, wmat, comm, vdeg, sl, constant):
    """The step's targets and Q from the quadratic _row_argmax over the
    same rows: move on a strictly positive gain, ties to the smaller id,
    the singleton guard; vertices without edges stay."""
    n_rows = dst.shape[0]
    nv = comm.shape[0]
    comm_deg = np.bincount(comm, weights=vdeg, minlength=nv).astype(
        np.float32)
    size = np.bincount(comm, minlength=nv)
    cmat = comm[dst]
    curr = comm[:n_rows]
    ref = _row_argmax(
        jnp.asarray(cmat), jnp.asarray(wmat), jnp.asarray(comm_deg[cmat]),
        None, jnp.asarray(curr), jnp.asarray(vdeg[:n_rows]),
        jnp.asarray(sl[:n_rows]),
        jnp.asarray(comm_deg[curr] - vdeg[:n_rows]),
        jnp.asarray(constant), SENTINEL)
    best_c = np.minimum(np.asarray(ref.best_c), nv - 1)
    guard = (size[best_c] == 1) & (size[curr] == 1) & (best_c > curr)
    move = (np.asarray(ref.best_gain) > 0) & ~guard
    target = comm.copy()
    target[:n_rows] = np.where(move, best_c, curr)
    c = np.float64(constant)
    q = (np.asarray(ref.counter0, np.float64).sum() * c
         - np.square(comm_deg.astype(np.float64) * c).sum())
    return target, q


def _heavy_rows(n_rows, width, nv, n_comm, seed, w_lo=1):
    """Random hub rows over ``nv`` vertices in ``n_comm`` communities:
    1/16-multiple weights (every f32 sum exact in any order, so the
    sorted and all-pairs aggregations agree bit for bit), half the rows
    with a self-loop slot, self-loop weights consistent with the rows."""
    rng = np.random.default_rng(seed)
    comm = rng.integers(0, n_comm, nv).astype(np.int32)
    dst = rng.integers(0, nv, (n_rows, width)).astype(np.int32)
    dst[: n_rows // 2, 0] = np.arange(n_rows // 2)
    wmat = (rng.integers(w_lo, 32, (n_rows, width)) / 16.0).astype(
        np.float32)
    vdeg = (rng.integers(1, 64, nv) / 4.0).astype(np.float32)
    sl = np.zeros(nv, np.float32)
    sl[:n_rows] = np.where(dst == np.arange(n_rows)[:, None], wmat,
                           0.0).sum(axis=1)
    return dst, wmat, comm, vdeg, sl


@pytest.mark.parametrize("seed", [0, 5])
@pytest.mark.parametrize("constant", [None, np.float32(0.3)])
def test_heavy_residual_matches_quadratic_oracle(seed, constant):
    """The sorted heavy residual of bucketed_step vs the quadratic
    all-pairs path on the same rows: identical targets, Q from the same
    counter0.  The non-dyadic constant=0.3 case also pins the gain's
    operand association to the all-pairs path's."""
    n_rows, width, nv = 64, 512, 512
    case = _heavy_rows(n_rows, width, nv, n_comm=200, seed=seed)
    constant = np.float32(1.0 / 1024) if constant is None else constant
    got, q = _heavy_step(*case, constant)
    want, q_ref = _heavy_oracle(*case, constant)
    assert np.array_equal(got, want)
    assert (got[:n_rows] != case[2][:n_rows]).sum() >= n_rows // 4
    assert q == pytest.approx(q_ref, rel=1e-6, abs=1e-6)


def test_heavy_residual_zero_weight_edges_are_candidates():
    """A community reached only by a w=0 edge is still a valid move target
    (same invariant as the degree classes: 'No w>0 filter'), and equal
    gains go to the smaller community id."""
    n_rows, width, nv = 16, 128, 128
    case = _heavy_rows(n_rows, width, nv, n_comm=60, seed=9, w_lo=0)
    assert (case[1] == 0).any()
    constant = np.float32(1.0 / 16.0)
    got, q = _heavy_step(*case, constant)
    want, q_ref = _heavy_oracle(*case, constant)
    assert np.array_equal(got, want)
    assert q == pytest.approx(q_ref, rel=1e-6, abs=1e-6)

    # Constructed rows.  Vertices 0 and 1 share community 0 with vertex
    # 4.  Vertex 0 has a w=0 edge into community 2 (degree 0.125,
    # positive gain) and a w=0.5 edge into community 3 (degree 40,
    # negative gain): the w=0-only community must win — valid means
    # present, not weighted.  Vertex 1 has equal-weight edges into the
    # singleton communities 5 and 6 of equal degree: the tie goes to 5.
    comm = np.array([0, 0, 2, 3, 0, 5, 6, 7], np.int32)
    vdeg = np.array([0.5, 0.5, 0.125, 40.0, 0.5, 2.0, 2.0, 1.0],
                    np.float32)
    dst = np.array([[2, 3], [5, 6]], np.int32)
    wmat = np.array([[0.0, 0.5], [0.25, 0.25]], np.float32)
    sl = np.zeros(8, np.float32)
    got, _q = _heavy_step(dst, wmat, comm, vdeg, sl, constant)
    want, _ = _heavy_oracle(dst, wmat, comm, vdeg, sl, constant)
    assert np.array_equal(got, want)
    assert got[0] == 2, "w=0-only community must be the argmax"
    assert got[1] == 5, "equal gains go to the smaller community id"


def test_heavy_residual_padding_and_no_candidates():
    """Padded slots (src == nv, w = 0) never contribute; rows whose
    neighbors all sit in the current community stay, and their weight
    is all counter0 (visible in Q)."""
    n_rows, width, nv = 8, 256, 128
    rng = np.random.default_rng(2)
    comm = (np.arange(nv) % 16).astype(np.int32)
    # Every slot of row r points at a vertex of r's own community.
    dst = (comm[:n_rows, None]
           + 16 * rng.integers(0, nv // 16, (n_rows, width))).astype(
        np.int32)
    wmat = np.full((n_rows, width), 0.5, np.float32)
    vdeg = np.ones(nv, np.float32)
    sl = np.zeros(nv, np.float32)
    sl[:n_rows] = np.where(dst == np.arange(n_rows)[:, None], wmat,
                           0.0).sum(axis=1)
    constant = np.float32(0.01)
    got, q = _heavy_step(dst, wmat, comm, vdeg, sl, constant)
    want, q_ref = _heavy_oracle(dst, wmat, comm, vdeg, sl, constant)
    assert np.array_equal(got, comm) and np.array_equal(want, comm)
    counter0 = 0.5 * width * n_rows
    deg = np.bincount(comm, weights=vdeg, minlength=nv)
    assert q == pytest.approx(
        counter0 * 0.01 - np.square(deg * 0.01).sum(), rel=1e-6)
    assert q == pytest.approx(q_ref, rel=1e-6)


def test_pallas_engine_end_to_end(karate):
    """engine='pallas' must produce the same result as engine='bucketed'
    through the full multi-phase driver (interpret mode on CPU)."""
    from cuvite_tpu.louvain.driver import louvain_phases

    res_b = louvain_phases(karate, engine="bucketed")
    res_p = louvain_phases(karate, engine="pallas")
    assert res_p.modularity == pytest.approx(res_b.modularity, abs=1e-6)
    assert np.array_equal(res_p.communities, res_b.communities)


def test_pallas_engine_rmat():
    from cuvite_tpu.io.generate import generate_rmat
    from cuvite_tpu.louvain.driver import louvain_phases

    g = generate_rmat(10, edge_factor=8, seed=4)
    res_b = louvain_phases(g, engine="bucketed")
    res_p = louvain_phases(g, engine="pallas")
    assert res_p.modularity == pytest.approx(res_b.modularity, abs=1e-5)


# ---------------------------------------------------------------------------
# The heavy class through the whole driver.


@pytest.fixture(scope="module")
def hub_graph():
    """A graph with one genuinely heavy vertex (> 8192 neighbors, the
    widths[-1] residual) plus background structure."""
    from cuvite_tpu.core.graph import Graph

    rng = np.random.default_rng(0)
    nv = 9000
    hub_dst = rng.choice(np.arange(1, nv), size=8400, replace=False)
    src = np.concatenate([np.zeros(8400, np.int64),
                          rng.integers(1, nv, 12000)])
    dst = np.concatenate([hub_dst, rng.integers(1, nv, 12000)])
    return Graph.from_edges(nv, src, dst)


# pallas arm ~29 s under the CPU interpreter; the heavy residual's
# parity stays tier-1 through the bucketed arm.
@pytest.mark.parametrize(
    "engine",
    ["bucketed", pytest.param("pallas", marks=pytest.mark.slow)])
def test_heavy_residual_full_run_matches_sort(hub_graph, engine):
    """A run whose hub takes the sorted heavy residual clusters
    bit-identically to the edge-slab sort engine: same phases,
    iterations per phase and labels.  Q agrees to float64 rounding: the
    sort engine evaluates it on the device (double-single), the
    bucketed engine on the host (float64)."""
    from cuvite_tpu.louvain.driver import louvain_phases

    r0 = louvain_phases(hub_graph, engine="sort")
    r1 = louvain_phases(hub_graph, engine=engine)
    assert len(r0.phases) == len(r1.phases) >= 2
    assert [p.iterations for p in r0.phases] \
        == [p.iterations for p in r1.phases]
    assert np.array_equal(r0.communities, r1.communities)
    assert r1.modularity == pytest.approx(r0.modularity, abs=1e-12)
    if engine == "bucketed":
        # No Pallas kernel runs, so the result carries no coverage.
        assert r1.pallas_coverage is None and r1.pallas_width_hits is None
    if engine == "pallas":
        # Coverage honesty: the heavy residual (width 0) is never
        # kernelised.
        assert r1.pallas_coverage < 1.0
        assert 0 not in r1.pallas_width_hits


def test_pallas_coverage_counts_heavy_residual_as_xla():
    """The per-phase coverage record: width 0 stands for the heavy
    class, whose sorted residual is never kernelised, so its edges count
    in the denominator only; coverage below one half warns."""
    from types import SimpleNamespace

    from cuvite_tpu.louvain.driver import PhaseRunner

    rec = SimpleNamespace()
    cov = [(8, 100, True), (4096, 150, False), (0, 150, False)]
    with pytest.warns(UserWarning, match="kernel-covered"):
        PhaseRunner._record_pallas_coverage(rec, cov)
    assert rec.pallas_coverage == 0.25
    assert rec.pallas_cov_detail == cov
