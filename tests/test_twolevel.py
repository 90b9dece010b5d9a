"""Two-level ICI/DCN exchange tests (ISSUE 18).

The tentpole contract, pinned three ways:

  * **Bit-identity.**  The two-level exchange changes WHERE community
    tables live (replicated only inside the fast ICI submesh), never
    what is computed: labels and modularity are bit-identical to the
    flat sparse exchange across every hybrid factorization of the
    8-device pool — through :func:`meshcheck.assert_mesh_neutral`, the
    one shared implementation.

  * **Plan structure.**  ``ExchangePlan.build_grouped`` degenerates to
    the flat plan at ici=1, remaps dst ids into group-local space, and
    reports per-axis stats (table_bytes_per_device, ghost_bytes).

  * **Sabotage.**  Re-widening one table's gather to the global axis
    MUST be convicted by M003's per-axis ``ici_replicated`` budget —
    measured on the traced step jaxpr at nv=8192, where the |dcn|-fold
    per-device inflation clears the law's tolerance-plus-floor
    allowance (at the 2048-vertex audit graph the gap hides under the
    4 KiB floor; a gate that cannot fail is not a gate).
"""

import os

import jax
import numpy as np
import pytest

from cuvite_tpu.analysis import meshcheck as mc
from cuvite_tpu.comm import exchange as xch
from cuvite_tpu.comm.mesh import make_hybrid_mesh
from cuvite_tpu.core.distgraph import DistGraph
from cuvite_tpu.io.generate import generate_rmat
from cuvite_tpu.louvain import driver as drv
from cuvite_tpu.louvain.driver import PhaseRunner, louvain_phases

HYBRID_SHAPES = ((8, 1), (4, 2), (2, 4), (1, 8))
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGET = os.path.join(REPO, mc.DEFAULT_BUDGET_REL)


def _labels(g, **kw):
    res = louvain_phases(g, max_phases=2, verbose=False, **kw)
    return [(np.asarray(res.communities), float(res.modularity))]


def _run_cfg(g):
    def run(cfg):
        if cfg == "flat":
            return _labels(g, nshards=8, engine="bucketed",
                           exchange="sparse")
        # exchange='auto' resolves to 'twolevel' when |dcn| > 1 and to
        # the flat sparse program at |dcn| == 1 — both paths covered.
        return _labels(g, nshards=8, engine="bucketed", exchange="auto",
                       mesh_shape=cfg)
    return run


def test_twolevel_bit_identical_to_flat():
    g = generate_rmat(10, edge_factor=8, seed=3)
    mc.assert_mesh_neutral(_run_cfg(g), ["flat", *HYBRID_SHAPES],
                           entry="twolevel_vs_flat")


@pytest.mark.slow
def test_twolevel_bit_identical_to_flat_rmat14():
    # The acceptance-scale pin: rmat-14 across every hybrid shape.
    g = generate_rmat(14, edge_factor=8, seed=3)
    mc.assert_mesh_neutral(_run_cfg(g), ["flat", *HYBRID_SHAPES],
                           entry="twolevel_vs_flat_rmat14")


# ---------------------------------------------------------------------------
# Grouped plan structure.


def test_grouped_plan_degenerates_to_flat_at_ici1():
    g = generate_rmat(8, edge_factor=8, seed=1)
    dg = DistGraph.build(g, 4)
    flat = xch.ExchangePlan.build(dg)
    grouped = xch.ExchangePlan.build_grouped(dg, 4)
    assert grouped.ici == 1
    assert grouped.nv_pad == flat.nv_pad
    for gg, gf in zip(grouped.ghost_ids, flat.ghost_ids):
        np.testing.assert_array_equal(gg, gf)
    np.testing.assert_array_equal(grouped.send_idx, flat.send_idx)
    # and remap_dst is the flat remap bit-for-bit
    s = 1
    src = np.asarray(dg.shards[s].src)
    dst = np.asarray(dg.shards[s].dst)
    np.testing.assert_array_equal(grouped.remap_dst(s, src, dst),
                                  flat.remap_dst(s, src, dst))


def test_grouped_plan_group_local_remap():
    g = generate_rmat(8, edge_factor=8, seed=1)
    dg = DistGraph.build(g, 8)
    plan = xch.ExchangePlan.build_grouped(dg, 2)  # ici = 4
    assert plan.ici == 4 and plan.nshards == 2
    nvp = dg.nv_pad
    nv_grp = plan.nv_pad
    assert nv_grp == 4 * nvp and plan.shard_nv_pad == nvp
    for s in range(8):
        grp = s // 4
        src = np.asarray(dg.shards[s].src)
        dst = np.asarray(dg.shards[s].dst)
        rd = np.asarray(plan.remap_dst(s, src, dst))
        real = src < nvp
        owned = real & (dst >= grp * nv_grp) & (dst < (grp + 1) * nv_grp)
        # owned dsts land at their group-local index; ghosts beyond
        np.testing.assert_array_equal(rd[owned],
                                      dst[owned] - grp * nv_grp)
        assert (rd[real & ~owned] >= nv_grp).all()
        # a shard's self edge remaps to (s % ici) * nvp + src — the
        # base build_stacked_plans must use for self-loop detection
        self_e = real & (dst == s * nvp + src)
        if self_e.any():
            np.testing.assert_array_equal(
                rd[self_e], (s % 4) * nvp + src[self_e])


def test_grouped_stats_report_per_axis_bytes():
    g = generate_rmat(8, edge_factor=8, seed=1)
    dg = DistGraph.build(g, 8)
    flat = xch.ExchangePlan.build(dg).stats()
    two = xch.ExchangePlan.build_grouped(dg, 2).stats()
    assert flat["mode"] == "sparse" and "dcn" not in flat
    assert two["mode"] == "twolevel"
    assert (two["dcn"], two["ici"]) == (2, 4)
    # group table window = nv_total / |dcn| per device, two tables wide
    assert two["table_bytes_per_device"] == \
        2 * dg.total_padded_vertices // 2 * 4
    assert two["ghost_bytes"] > 0


def test_result_carries_exchange_stats():
    # The bench/CLI `exchange` block's source (ISSUE 18 satellite): an
    # SPMD run's result carries the phase-1 plan digest; single-shard
    # runs carry None.
    g = generate_rmat(8, edge_factor=8, seed=1)
    two = louvain_phases(g, mesh_shape=(2, 4), engine="bucketed",
                         max_phases=1, verbose=False)
    xs = two.exchange_stats
    assert xs["mode"] == "twolevel"
    assert (xs["dcn"], xs["ici"]) == (2, 4)
    assert xs["table_bytes_per_device"] > 0 and xs["ghost_bytes"] > 0
    flat = louvain_phases(g, nshards=8, engine="bucketed",
                          exchange="sparse", max_phases=1, verbose=False)
    assert flat.exchange_stats["mode"] == "sparse"
    solo = louvain_phases(g, engine="bucketed", max_phases=1,
                          verbose=False)
    assert solo.exchange_stats is None


def test_twolevel_validation_errors():
    g = generate_rmat(8, edge_factor=8, seed=1)
    with pytest.raises(ValueError, match="mesh_shape"):
        louvain_phases(g, nshards=4, mesh_shape=(2, 4))
    with pytest.raises(ValueError, match="twolevel"):
        louvain_phases(g, nshards=8, exchange="twolevel")
    with pytest.raises(ValueError, match="replicated"):
        louvain_phases(g, mesh_shape=(2, 4), exchange="replicated")
    with pytest.raises(ValueError, match="coloring"):
        louvain_phases(g, mesh_shape=(2, 4), coloring=2)


# ---------------------------------------------------------------------------
# The M003 per-axis sabotage: one table re-widened to the global axis.


def _trace_table_row(nv, shape):
    """exchange_tables ledger row of the step jaxpr traced at ``shape``
    on a ``nv``-vertex audit-style graph (trace only — no execution)."""
    from cuvite_tpu.analysis.jaxpr_audit import tiny_graphs

    n_dcn, n_ici = shape
    g = tiny_graphs(b=1, nv=nv, ne=4 * nv)[0]
    dg = DistGraph.build(g, n_dcn * n_ici)
    runner = PhaseRunner(dg, mesh=make_hybrid_mesh(n_dcn, n_ici),
                         engine="bucketed", exchange="twolevel")
    jaxpr = jax.make_jaxpr(
        lambda c: runner._call(c, runner._extra))(runner.comm0)
    return mc.exchange_table_bytes(jaxpr, {"dcn": n_dcn, "ici": n_ici})


def test_global_axis_table_convicted_by_per_axis_budget(monkeypatch):
    nv, shape = 8192, (4, 2)
    honest = _trace_table_row(nv, shape)
    # honest: two group tables (comm + vdeg) at nv/|dcn| each
    assert honest["per_device"] == 2 * nv // 4 * 4
    assert honest["global"] == 2 * nv * 4

    real = xch.twolevel_env

    def widened(comm, vdeg, send_idx, ghost_sel, dcn_axis, ici_axis,
                **kw):
        env = real(comm, vdeg, send_idx, ghost_sel, dcn_axis, ici_axis,
                   **kw)
        # the sabotage: one community table gathered over BOTH axes —
        # O(nv_total) per device again, exactly what two-level removed.
        wide = jax.lax.all_gather(comm, (dcn_axis, ici_axis), tiled=True)
        n = env.cdeg_v.shape[0]
        return env._replace(
            cdeg_v=env.cdeg_v + 0 * wide[:n].astype(env.cdeg_v.dtype))

    monkeypatch.setattr(xch, "twolevel_env", widened)
    drv._STEP_CACHE.clear()
    try:
        sabotaged = _trace_table_row(nv, shape)
    finally:
        drv._STEP_CACHE.clear()
    assert sabotaged["per_device"] == honest["per_device"] + nv * 4

    manifest = mc.load_budget(BUDGET)
    axes = {"dcn": shape[0], "ici": shape[1]}

    def row(r):
        return {"4x2": {"devices": 8, "axes": axes,
                        "categories": {"exchange_tables": r}}}

    assert mc.check_replication("twolevel", row(honest), manifest) == []
    findings = mc.check_replication("twolevel", row(sabotaged), manifest)
    assert [f.rule for f in findings] == ["M003"], findings
    assert "ici_replicated" in findings[0].message


def test_exchange_table_bytes_counts_replicating_collectives_only():
    """The metric's ground rules on a hand-built jaxpr: all_gather and
    non-scalar psum count; all_to_all (distinct data per device) and
    scalar psums do not."""
    from functools import partial

    from cuvite_tpu.comm.mesh import make_mesh
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(8)

    @partial(jax.shard_map, mesh=mesh, in_specs=P("v"), out_specs=P(),
             check_vma=False)
    def body(x):
        g = jax.lax.all_gather(x, "v", tiled=True)      # 8*16*4 = 512 B  # graftlint: disable=R025 — hand-built fixture exercising the exchange_table_bytes metric, not a product table
        t = jax.lax.psum(x, "v")                        # 16*4 = 64 B
        s = jax.lax.psum(jax.numpy.sum(x), "v")         # scalar: 0
        a = jax.lax.all_to_all(x.reshape(8, 2), "v", 0, 0)  # moved: 0
        return g.sum() + t.sum() + s + a.sum()

    jaxpr = jax.make_jaxpr(body)(np.zeros(128, np.float32))
    row = mc.exchange_table_bytes(jaxpr, {"v": 8})
    assert row["per_device"] == 512 + 64
    # the gather spans the whole axis (1 distinct copy); the psum'd
    # table is replicated 8-fold but covers its extent once
    assert row["global"] == 512 + 64
