"""Tier-5 mesh audit tests (ISSUE 15): the dynamic M001-M003 gate and
its sabotage fixtures.

The acceptance gate is :func:`test_mesh_audit_green_on_current_tree`:
labels bit-identical across >= 3 virtual mesh shapes for both solo
exchanges and both batched engines, per-shard collective sequences
identical, and the per-device HBM ledger obeying every scaling law in
``tools/replication_budget.json``.  The sabotage tests then prove each
M-rule actually convicts a seeded bug — a gate that cannot fail is not
a gate:

  * a conditional psum (collectives under branch-divergent control
    flow) MUST trip M001;
  * a mesh-shape-forked collective schedule MUST trip M001;
  * shape-divergent labels MUST trip M002;
  * an unsharded table threaded into a sharded entry MUST trip M003
    (driver placements monkeypatched to replicate — the ledger's
    per-device column sees through it);
  * dynamic M00x results are NEVER written to the incremental lint
    cache (the concheck precedent).
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from cuvite_tpu.analysis import meshcheck as mc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGET = os.path.join(REPO, "tools", "replication_budget.json")


# ---------------------------------------------------------------------------
# THE tier-1 gate: the full audit on the forced-CPU 8-virtual-device
# shape (conftest pins the device count; the same audit tools/
# mesh_audit.py runs standalone).


def test_mesh_audit_green_on_current_tree():
    findings, reports = mc.run_mesh_audit()
    assert not findings, "\n".join(f.format() for f in findings)
    # Coverage, not vacuity: every entry observed at every shape, the
    # sparse entries exchange via all_to_all, the batched programs are
    # collective-free by design, and the ledger rows are non-trivial.
    assert set(reports) == set(mc.ENTRIES)
    for name, by_shape in reports.items():
        assert len(by_shape) == len(mc.MESH_SHAPES), name
        for rep in by_shape.values():
            assert rep.labels and rep.categories, (name, rep.tag)
    sparse_seq = reports["bucketed_sparse"]["8x1"].seq
    assert any(p == "all_to_all" for p, _ in sparse_seq), \
        "sparse entry must exchange via all_to_all"
    assert reports["bucketed_replicated"]["8x1"].seq != sparse_seq
    assert reports["batched_fused"]["8x1"].seq == (), \
        "the batched program is collective-free by design"
    # Two-level entry: tables gathered on the fast axis, ghosts routed
    # on the slow one, and per-device table bytes shrinking ~1/|dcn|
    # (the tentpole's whole point — 2x at |dcn|=2, 4x at |dcn|=4).
    two_sigs = mc._flat_sigs(reports["bucketed_twolevel"]["4x2"].seq)
    assert any(s == "all_gather(ici)" for s in two_sigs), two_sigs
    assert any(s == "all_to_all(dcn)" for s in two_sigs), two_sigs
    floors = {2: 1.8, 4: 3.5, 8: 7.0}
    for tag, rep in reports["bucketed_twolevel"].items():
        row = rep.categories["exchange_tables"]
        ratio = row["global"] / row["per_device"]
        assert ratio >= floors[rep.axes["dcn"]], (tag, row)


def test_budget_manifest_closed_and_loadable():
    doc = mc.load_budget(BUDGET)
    assert doc["version"] == mc.BUDGET_VERSION
    for cat in ("slab", "tables", "plans", "exchange", "scratch"):
        assert doc["categories"][cat]["law"] in ("sharded", "replicated")
    # v2: the two-level categories carry the per-axis law — tables and
    # grouped routing may reach full extent over |dcn|, never more.
    for cat in ("exchange_tables", "exchange_grouped"):
        assert doc["categories"][cat]["law"] == "ici_replicated"


def test_missing_budget_fails_closed(tmp_path):
    findings, _ = mc.run_mesh_audit(
        entry_names=[], budget_path=str(tmp_path / "nope.json"))
    assert [f.rule for f in findings] == ["M000"]


# ---------------------------------------------------------------------------
# Sabotage: M001 — the conditional psum.


def test_conditional_psum_trips_m001():
    from jax.sharding import PartitionSpec as P

    from cuvite_tpu.comm.mesh import make_mesh

    mesh = make_mesh(8)

    def bad(x):
        return jax.lax.cond(
            x[0] > 0.0,
            lambda v: jax.lax.psum(v, "v"),
            lambda v: v,
            x)

    wrapped = jax.jit(jax.shard_map(bad, mesh=mesh, in_specs=P("v"),
                                out_specs=P("v"), check_vma=False))
    jaxpr = jax.make_jaxpr(wrapped)(np.zeros(8, np.float32))
    findings = mc.lint_collective_jaxpr(jaxpr, "sabotage_cond_psum")
    assert any(f.rule == "M001" for f in findings), findings

    def good(x):  # both branches issue the identical sequence
        return jax.lax.cond(
            x[0] > 0.0,
            lambda v: jax.lax.psum(v, "v"),
            lambda v: jax.lax.psum(v * 0.0, "v"),
            x)

    wrapped_ok = jax.jit(jax.shard_map(good, mesh=mesh, in_specs=P("v"),
                                   out_specs=P(), check_vma=False))
    jaxpr_ok = jax.make_jaxpr(wrapped_ok)(np.zeros(8, np.float32))
    assert not mc.lint_collective_jaxpr(jaxpr_ok, "balanced_cond")


def test_sequence_with_empty_cond_branch_flattens_and_convicts():
    """The conditional-psum shape produces a cond with one EMPTY
    branch; flattening and cross-shape comparison must convict, not
    crash (review regression: _flat_names IndexError on ())."""
    forked = {"8x1": (("cond", ((("psum", ("v",)),), ())),),
              "4x2": ()}
    findings = mc.check_sequences("e", forked)
    assert [f.rule for f in findings] == ["M001"]
    assert "psum" in findings[0].message
    # ... and branch flattening keeps EVERY collective, including the
    # first of each branch.
    seq = (("cond", ((("psum", ("v",)), ("all_to_all", ("v",))),
                     (("all_gather", ("v",)),))),)
    assert mc._flat_names(seq) == ["psum", "all_to_all", "all_gather"]


def test_shape_forked_sequence_trips_m001():
    seqs = {"8x1": (("psum", ("v",)), ("all_to_all", ("v",))),
            "4x2": (("psum", ("v",)),)}
    findings = mc.check_sequences("forked", seqs)
    assert [f.rule for f in findings] == ["M001"]
    assert not mc.check_sequences("same", {"8x1": seqs["8x1"],
                                           "4x2": seqs["8x1"]})


def test_axis_renamed_sequence_convicts_with_axes_in_message():
    """Sequences differing ONLY in axis names — the ICI/DCN rename
    class — must convict AND the message must render the axes (review
    regression: names-only rendering read 'psum vs psum')."""
    seqs = {"8x1": (("psum", ("v",)),), "4x2": (("psum", ("ici",)),)}
    findings = mc.check_sequences("renamed", seqs)
    assert [f.rule for f in findings] == ["M001"]
    assert "psum(v)" in findings[0].message
    assert "psum(ici)" in findings[0].message


def test_shape_divergent_labels_trip_m002():
    a = np.arange(16)
    b = a.copy()
    b[3] = 0
    findings = mc.check_labels("lab", {"8x1": [(a, 0.5)],
                                       "4x2": [(b, 0.5)]})
    assert [f.rule for f in findings] == ["M002"]
    findings_q = mc.check_labels("labq", {"8x1": [(a, 0.5)],
                                          "4x2": [(a, 0.5000001)]})
    assert [f.rule for f in findings_q] == ["M002"]
    assert not mc.check_labels("ok", {"8x1": [(a, 0.5)],
                                      "4x2": [(a.copy(), 0.5)]})


# ---------------------------------------------------------------------------
# Sabotage: M003 — an unsharded [nv_pad] table inside a sharded entry.
# driver placements are monkeypatched to REPLICATE; the ledger's
# per-device column must stop scaling and the law check must convict.


def test_unsharded_table_trips_m003(monkeypatch):
    import cuvite_tpu.louvain.driver as drv
    from cuvite_tpu.comm.mesh import make_mesh, shard_1d
    from cuvite_tpu.core.distgraph import DistGraph
    from cuvite_tpu.louvain.driver import PhaseRunner

    monkeypatch.setattr(
        drv, "shard_1d",
        lambda mesh, arr, replicate=False: shard_1d(mesh, arr,
                                                    replicate=True))
    ledgers = {}
    for shape in ((4, 2), (2, 4)):
        dg = DistGraph.build(mc._audit_graph(), shape[0])
        rec, tracer = mc._recorder()
        PhaseRunner(dg, mesh=make_mesh(shape[0]), engine="bucketed",
                    exchange="replicated", tracer=tracer)
        rec.ledger.snapshot(0)
        ledgers[f"{shape[0]}x{shape[1]}"] = {
            "devices": shape[0],
            "categories": mc._ledger_categories(rec.ledger),
        }
    findings = mc.check_replication("sabotage_replicated",
                                    ledgers, mc.load_budget(BUDGET))
    assert any(f.rule == "M003" for f in findings), ledgers
    assert any("tables" in (f.snippet or "") for f in findings
               if f.rule == "M003")


def test_unlisted_category_trips_m003():
    ledgers = {"4x2": {"devices": 4, "categories": {
        "mystery": {"global": 1 << 20, "per_device": 1 << 18}}}}
    findings = mc.check_replication("x", ledgers, mc.load_budget(BUDGET))
    assert [f.rule for f in findings] == ["M003"]
    assert "mystery" in findings[0].message


def test_per_device_nbytes_sees_replication():
    """The ledger export itself: a replicated placement answers full
    bytes per device, a 1-D sharded one 1/S — the measurement M003's
    law check is built on."""
    from cuvite_tpu.comm.mesh import make_mesh, shard_1d
    from cuvite_tpu.obs.memory import per_device_nbytes

    mesh = make_mesh(4)
    host = np.zeros(4096, np.float32)
    sharded = shard_1d(mesh, host)
    replicated = shard_1d(mesh, host, replicate=True)
    assert per_device_nbytes(sharded) == host.nbytes // 4
    assert per_device_nbytes(replicated) == host.nbytes
    assert per_device_nbytes(host) == host.nbytes  # host: conservative


# ---------------------------------------------------------------------------
# Dynamic results are never cached.


def test_mesh_audit_never_touches_lint_cache(tmp_path):
    from cuvite_tpu.analysis.engine import run_paths

    cache = tmp_path / "cache.json"
    src = tmp_path / "m.py"
    src.write_text("x = 1\n")
    run_paths([str(src)], cache=str(cache))
    before = cache.read_bytes()
    findings, _ = mc.run_mesh_audit(
        entry_names=["bucketed_replicated"],
        shapes=((4, 2), (2, 4)))
    assert not findings
    assert cache.read_bytes() == before, \
        "dynamic M00x results must never enter the lint cache"


# ---------------------------------------------------------------------------
# The shared neutrality helper (what test_batched/test_pallas_spmd use).


def test_assert_mesh_neutral_helper():
    good = {"a": [(np.arange(4), 0.1)], "b": [(np.arange(4), 0.1)]}
    mc.assert_mesh_neutral(lambda cfg: good[cfg], ["a", "b"])
    bad = {"a": [(np.arange(4), 0.1)], "b": [(np.arange(4) * 2, 0.1)]}
    with pytest.raises(AssertionError, match="M002"):
        mc.assert_mesh_neutral(lambda cfg: bad[cfg], ["a", "b"])


# ---------------------------------------------------------------------------
# CLI: the static --inventory path stays runnable without the audit
# (subprocess; the full-audit CLI is exercised in-process above).


def test_mesh_audit_cli_write_budget(tmp_path):
    """The M000 remediation path is real: --write-budget regenerates
    the manifest, preserving existing category laws."""
    budget = tmp_path / "budget.json"
    budget.write_text(json.dumps({
        "version": 1, "env": {},
        "categories": {"slab": {"law": "sharded", "reason": "seeded"}},
    }))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mesh_audit.py"),
         "--write-budget", "--entries", "bucketed_replicated",
         "--shapes", "2x1", "--budget", str(budget)],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    doc = json.loads(budget.read_text())
    assert doc["version"] == mc.BUDGET_VERSION
    assert doc["categories"]["slab"]["reason"] == "seeded"
    # observed-but-unlisted categories land with the failing-closed
    # 'sharded' default law.
    assert any(v["law"] == "sharded" and "autogenerated" in v["reason"]
               for k, v in doc["categories"].items() if k != "slab")


def test_mesh_audit_cli_inventory_subprocess():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mesh_audit.py"),
         "--inventory", "--json"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    inv = json.loads(out.stdout)
    rels = {e["rel"] for e in inv}
    # The replicated community tables are in the closed inventory.
    assert "cuvite_tpu/louvain/bucketed.py" in rels
    assert "cuvite_tpu/ops/segment.py" in rels
    assert all(e["reason"] for e in inv)
