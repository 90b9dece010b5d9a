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


@pytest.mark.parametrize("seed", [0, 5])
@pytest.mark.parametrize("constant", [None, np.float32(0.3)])
def test_heavy_bincount_matches_quadratic_oracle(seed, constant):
    """Heavy-class community-range-tile kernel (heavy_bincount.py) vs the
    quadratic XLA fallback on the same rows: identical best_c/best_gain/
    counter0 bit-for-bit (1/16-multiple weights make f32 sums exact in any
    order, so the matmul-bincount and the all-pairs aggregation agree;
    the non-dyadic constant=0.3 case additionally pins the gain's operand
    association to the XLA path's)."""
    from cuvite_tpu.kernels.heavy_bincount import heavy_argmax_pallas

    n_rows, width, nv = 64, 512, 500
    nv_ceil, c_tile, d_chunk = 512, 128, 128
    cmat, wmat, curr, vdeg, sl, comm_deg, _const_dyadic = _bucket_case(
        n_rows, width, nv, seed)
    constant = _const_dyadic if constant is None else constant
    is_cc = cmat == curr[:, None]
    counter0 = np.sum(np.where(is_cc, wmat, 0.0), axis=1).astype(np.float32)
    ay = comm_deg[cmat]
    ax = comm_deg[curr] - vdeg
    ref = _row_argmax(
        jnp.asarray(cmat), jnp.asarray(wmat), jnp.asarray(ay), None,
        jnp.asarray(curr), jnp.asarray(vdeg), jnp.asarray(sl),
        jnp.asarray(ax), jnp.asarray(constant), SENTINEL,
    )
    comm_deg_pad = np.zeros(nv_ceil, dtype=np.float32)
    comm_deg_pad[:nv] = comm_deg
    bc, bg, c0 = heavy_argmax_pallas(
        jnp.asarray(np.ascontiguousarray(cmat.T)),
        jnp.asarray(np.ascontiguousarray(wmat.T)),
        jnp.asarray(comm_deg_pad),
        jnp.asarray(curr), jnp.asarray(vdeg), jnp.asarray(sl),
        jnp.asarray(ax), jnp.asarray(constant),
        c_tile=c_tile, d_chunk=d_chunk, interpret=True,
    )
    assert np.array_equal(np.asarray(c0), counter0)
    assert np.array_equal(np.asarray(bg), np.asarray(ref.best_gain))
    assert np.array_equal(np.asarray(bc), np.asarray(ref.best_c))


def test_heavy_bincount_zero_weight_edges_are_candidates():
    """A community reached only by a w=0 edge is still a valid move target
    (same invariant as the XLA paths: 'No w>0 filter').  Its gain
    -2*eix - 2*vdeg*const*(ay-ax) can win when ay < ax."""
    from cuvite_tpu.kernels.heavy_bincount import heavy_argmax_pallas

    n_rows, width, nv = 16, 128, 120
    nv_ceil, c_tile, d_chunk = 128, 128, 128
    rng = np.random.default_rng(9)
    cmat = rng.integers(0, nv, size=(n_rows, width)).astype(np.int32)
    wmat = (rng.integers(0, 4, size=(n_rows, width)) / 16.0).astype(
        np.float32)  # ~1/4 of edges have weight 0
    curr = rng.integers(0, nv, size=n_rows).astype(np.int32)
    vdeg = np.maximum(wmat.sum(axis=1), 0.25).astype(np.float32)
    sl = np.zeros(n_rows, dtype=np.float32)
    comm_deg = (rng.integers(1, 64, size=nv) / 8.0).astype(np.float32)
    ay = comm_deg[cmat]
    ax = comm_deg[curr] - vdeg
    constant = np.float32(1.0 / 16.0)
    ref = _row_argmax(
        jnp.asarray(cmat), jnp.asarray(wmat), jnp.asarray(ay), None,
        jnp.asarray(curr), jnp.asarray(vdeg), jnp.asarray(sl),
        jnp.asarray(ax), jnp.asarray(constant), SENTINEL,
    )
    cdp = np.zeros(nv_ceil, dtype=np.float32)
    cdp[:nv] = comm_deg
    bc, bg, c0 = heavy_argmax_pallas(
        jnp.asarray(np.ascontiguousarray(cmat.T)),
        jnp.asarray(np.ascontiguousarray(wmat.T)),
        jnp.asarray(cdp),
        jnp.asarray(curr), jnp.asarray(vdeg), jnp.asarray(sl),
        jnp.asarray(ax), jnp.asarray(constant),
        c_tile=c_tile, d_chunk=d_chunk, interpret=True,
    )
    assert np.array_equal(np.asarray(bg), np.asarray(ref.best_gain))
    assert np.array_equal(np.asarray(bc), np.asarray(ref.best_c))

    # Constructed row where a community reached ONLY by a w=0 edge WINS:
    # pins valid = (cnt > 0), not (wagg > 0) — the old rule returns
    # community 2 here.  curr=0, no edges into it (eix=0); community 1
    # via w=0 (tiny comm_deg -> positive gain), community 2 via w=0.5
    # (huge comm_deg -> negative gain).
    one = np.full((1, 128), nv_ceil, dtype=np.int32)
    onew = np.zeros((1, 128), dtype=np.float32)
    one[0, 0], onew[0, 0] = 1, 0.0
    one[0, 1], onew[0, 1] = 2, 0.5
    cd1 = np.ones(nv_ceil, dtype=np.float32)
    cd1[1], cd1[2] = 0.125, 40.0
    bc1, bg1, c01 = heavy_argmax_pallas(
        jnp.asarray(one.T.copy()), jnp.asarray(onew.T.copy()),
        jnp.asarray(cd1),
        jnp.asarray(np.array([0], np.int32)),
        jnp.asarray(np.array([0.5], np.float32)),
        jnp.asarray(np.array([0.0], np.float32)),
        jnp.asarray(np.array([0.5], np.float32)),  # ax = cd[0] - vdeg
        jnp.asarray(np.float32(1 / 16)),
        c_tile=c_tile, d_chunk=d_chunk, interpret=True,
    )
    assert int(bc1[0]) == 1, "w=0-only community must be the argmax"
    assert float(bg1[0]) == 2 * 0.5 * (1 / 16) * (0.5 - 0.125)
    assert float(c01[0]) == 0.0


def test_heavy_bincount_padding_and_no_candidates():
    """Padded slots (c = nv_ceil, w = 0) never contribute; rows whose
    neighbors all sit in the current community return the sentinel."""
    from cuvite_tpu.kernels.heavy_bincount import heavy_argmax_pallas

    n_rows, width = 8, 256
    nv, nv_ceil, c_tile, d_chunk = 100, 128, 128, 128
    rng = np.random.default_rng(2)
    curr = rng.integers(0, nv, size=n_rows).astype(np.int32)
    cmat = np.full((n_rows, width), nv_ceil, dtype=np.int32)  # all padding
    wmat = np.zeros((n_rows, width), dtype=np.float32)
    # First half of the slots: real edges into the CURRENT community only.
    cmat[:, : width // 2] = curr[:, None]
    wmat[:, : width // 2] = 0.5
    vdeg = np.ones(n_rows, dtype=np.float32)
    sl = np.zeros(n_rows, dtype=np.float32)
    comm_deg = np.ones(nv_ceil, dtype=np.float32)
    ax = comm_deg[curr] - vdeg
    bc, bg, c0 = heavy_argmax_pallas(
        jnp.asarray(np.ascontiguousarray(cmat.T)),
        jnp.asarray(np.ascontiguousarray(wmat.T)),
        jnp.asarray(comm_deg),
        jnp.asarray(curr), jnp.asarray(vdeg), jnp.asarray(sl),
        jnp.asarray(ax), jnp.asarray(np.float32(0.01)),
        c_tile=c_tile, d_chunk=d_chunk, interpret=True,
    )
    assert np.all(np.asarray(bc) == SENTINEL)
    assert np.all(np.isneginf(np.asarray(bg)))
    assert np.allclose(np.asarray(c0), 0.5 * (width // 2))


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
# ISSUE 8: the heavy-class kernel promotion — layout builder, policy, and
# the compiled-path (jitted driver, interpret kernel) parity pin.


def test_build_heavy_layout_contract():
    from cuvite_tpu.kernels.heavy_bincount import build_heavy_layout

    nv_local, pad_id = 64, 4096
    # CSR-ordered padded triples: vertex 3 (4 edges), vertex 7 (2 edges).
    hs = np.array([3, 3, 3, 3, 7, 7, 64, 64], np.int64)
    hd = np.array([10, 11, 12, 13, 20, 21, 0, 0], np.int64)
    hw = np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 0, 0], np.float32)
    verts, dT, wT = build_heavy_layout(hs, hd, hw, nv_local=nv_local,
                                       pad_id=pad_id, d_chunk=8)
    assert verts.shape == (8,) and dT.shape == wT.shape == (8, 8)
    assert list(verts[:2]) == [3, 7] and (verts[2:] == nv_local).all()
    assert list(dT[:4, 0]) == [10, 11, 12, 13]
    assert list(wT[:2, 1]) == [5.0, 6.0]
    # Padding slots: dst == pad_id (never a candidate), w == 0.
    assert (dT[4:, 0] == pad_id).all() and (wT[2:, 1] == 0).all()
    assert (dT[:, 2:] == pad_id).all()
    # Element budget: an over-budget hub set degrades to None.
    assert build_heavy_layout(hs, hd, hw, nv_local=nv_local,
                              pad_id=pad_id, d_chunk=8,
                              max_elems=16) is None
    # No heavy edges at all -> None.
    empty = np.full(8, nv_local, np.int64)
    assert build_heavy_layout(empty, hd, hw, nv_local=nv_local,
                              pad_id=pad_id) is None


def test_heavy_kernel_policy(monkeypatch):
    from cuvite_tpu.kernels.heavy_bincount import heavy_kernel_enabled

    monkeypatch.delenv("CUVITE_HEAVY_KERNEL", raising=False)
    # Opt-in on every backend: the sorted path won on the chip (PR 21).
    assert heavy_kernel_enabled() is False
    monkeypatch.setenv("CUVITE_HEAVY_KERNEL", "0")
    assert heavy_kernel_enabled() is False
    monkeypatch.setenv("CUVITE_HEAVY_KERNEL", "1")   # forced (interpret)
    assert heavy_kernel_enabled() is True


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


# pallas arm ~29 s under the CPU interpreter; the kernel's bit-identity
# stays tier-1 through the bucketed arm + the unit-level kernel tests.
@pytest.mark.parametrize(
    "engine",
    ["bucketed", pytest.param("pallas", marks=pytest.mark.slow)])
def test_heavy_kernel_full_run_bit_identical(hub_graph, engine,
                                             monkeypatch):
    """The opt-in heavy path (CUVITE_HEAVY_KERNEL=1 runs the kernel in
    interpret mode on CPU — the same jitted driver path the chip runs
    compiled) must cluster bit-identically to the sorted heavy
    path it replaces."""
    from cuvite_tpu.louvain.driver import louvain_phases

    monkeypatch.setenv("CUVITE_HEAVY_KERNEL", "0")
    r0 = louvain_phases(hub_graph, engine=engine)
    monkeypatch.setenv("CUVITE_HEAVY_KERNEL", "1")
    r1 = louvain_phases(hub_graph, engine=engine)
    assert len(r0.phases) == len(r1.phases) >= 2
    assert r0.total_iterations == r1.total_iterations
    assert r0.modularity == r1.modularity
    assert np.array_equal(r0.communities, r1.communities)
    if engine == "pallas":
        # Coverage honesty: with the heavy kernel engaged the heavy
        # residual (width 0) counts as kernelized; without it, not.
        assert r1.pallas_coverage > r0.pallas_coverage
        assert 0 in r1.pallas_width_hits


def test_heavy_kernel_budget_degrade_keeps_sorted_path(hub_graph,
                                                       monkeypatch):
    """An over-budget hub layout must degrade loudly to the sorted path
    and still produce the identical clustering (the PALLAS_MAX_WIDTH
    degrade-with-coverage pattern)."""
    from cuvite_tpu.louvain.driver import louvain_phases

    monkeypatch.setenv("CUVITE_HEAVY_KERNEL", "0")
    r0 = louvain_phases(hub_graph, engine="bucketed")
    monkeypatch.setenv("CUVITE_HEAVY_KERNEL", "1")
    monkeypatch.setenv("CUVITE_HEAVY_ELEMS", "64")
    with pytest.warns(UserWarning, match="CUVITE_HEAVY_ELEMS"):
        r1 = louvain_phases(hub_graph, engine="bucketed")
    assert np.array_equal(r0.communities, r1.communities)
