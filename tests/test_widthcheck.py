"""Tier-1 gate for graftlint tier 6: widthcheck (R026-R028 static) +
the width audit (W001-W003 dynamic).

Four layers:

  * the audit itself must be green on the current tree — the scale-28
    zero-allocation certification IS a tier-1 test;
  * sabotage fixtures prove every rule convicts a seeded overflow
    (a gate that cannot fail is not a gate);
  * the width summaries ride the tier-2 lint cache bit-identically
    warm vs cold, while dynamic W00x results never enter it;
  * the single-source pins: widthcheck.MAX_WORKLOAD ==
    registry.max_workload(), BATCH_MAX == max(BATCH_SIZES), the
    width-ok inventory closed, R026-R028 present in SARIF.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cuvite_tpu.analysis import widthaudit as wa
from cuvite_tpu.analysis import widthcheck as wc
from cuvite_tpu.analysis.callgraph import run_project_sources

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------------------
# The audit on the current tree (the certification gate).


def test_width_audit_green_on_current_tree():
    findings, reports = wa.run_width_audit()
    assert not findings, "\n".join(f.format() for f in findings)
    # Both certification workloads traced every entry.
    for wname in ("friendster", "rmat_s28"):
        assert set(reports[wname]) == set(wa.ENTRIES)
    # The zero-allocation pin: tracing the billion-edge path touched
    # NO device memory.
    assert reports["spy"]["delta_bytes"] == 0


def test_audit_workloads_derive_from_registry():
    from cuvite_tpu.workloads import registry

    wl = wa.audit_workloads()
    s28 = wl[f"rmat_s{registry.RMAT_SCALE_MAX}"]
    nv, ne = registry.rmat_scale_law(registry.RMAT_SCALE_MAX)
    assert s28["nv_pad"] == nv and s28["ne_pad"] == ne  # pow2 already
    # Every per-shard slab is admissible under the raise-guard.
    from cuvite_tpu.ops.segment import SLAB_NE_MAX

    for shapes in wl.values():
        assert shapes["ne_shard"] <= SLAB_NE_MAX
        assert shapes["ne_shard"] * shapes["shards"] == shapes["ne_pad"]


def test_max_workload_single_source():
    from cuvite_tpu.core.batch import BATCH_SIZES
    from cuvite_tpu.workloads import registry

    assert registry.max_workload() == wc.MAX_WORKLOAD
    assert registry.BATCH_MAX == max(BATCH_SIZES)


# ---------------------------------------------------------------------------
# Static sabotage: R026/R027/R028 each convict a seeded overflow.


def _lint(src: str, rel: str = "cuvite_tpu/ops/sab.py"):
    return run_project_sources({rel: src})


def test_r026_convicts_int32_slab_domain():
    findings = _lint(
        "import jax.numpy as jnp\n"
        "def flat(ne_pad):\n"
        "    idx = jnp.arange(ne_pad * ne_pad, dtype=jnp.int32)\n"
        "    return idx\n")
    assert "R026" in _rules(findings)


def test_r026_skips_raise_guarded_site():
    findings = _lint(
        "import jax.numpy as jnp\n"
        "CEIL = 1 << 30\n"
        "def flat(src):\n"
        "    ne_pad = src.shape[0]\n"
        "    if ne_pad > CEIL:\n"
        "        raise ValueError('shard the slab first')\n"
        "    idx = jnp.arange(ne_pad, dtype=jnp.int32)\n"
        "    brk = (idx != 0).astype(jnp.int32)\n"
        "    rid = jnp.cumsum(brk)\n"
        "    return rid\n")
    assert not findings, "\n".join(f.format() for f in findings)


def test_r027_convicts_untied_pack():
    findings = _lint(
        "def pack(src, ckey, kbits):\n"
        "    return (src << kbits) | ckey\n")
    assert "R027" in _rules(findings)


def test_r027_skips_pack_tied_to_guard():
    # The segment.py contract shape: the pack sits under a predicate
    # derived from the shift amount's own bit budget.
    findings = _lint(
        "def pack(src, ckey, key_bound, src_bound):\n"
        "    kbits = max(key_bound - 1, 1).bit_length()\n"
        "    sbits = max(src_bound - 1, 1).bit_length()\n"
        "    fits32 = kbits + sbits <= 31\n"
        "    if fits32:\n"
        "        return (src << kbits) | ckey\n"
        "    return None\n")
    assert "R027" not in _rules(findings)


def test_bare_pow2_shift_is_not_a_pack():
    # `1 << bit_length()` pow2 padding (next_pow2, pow2_floor, tree-sum
    # padding) must not read as a bit-pack.
    findings = _lint(
        "def next_pow2(n):\n"
        "    if n <= 1:\n"
        "        return 1\n"
        "    return 1 << (int(n - 1).bit_length())\n")
    assert not findings


def test_r028_convicts_int32_slab_reduction():
    findings = _lint(
        "import jax.numpy as jnp\n"
        "def run_ids(src):\n"
        "    brk = (src[1:] != src[:-1]).astype(jnp.int32)\n"
        "    return jnp.cumsum(brk)\n")
    assert "R028" in _rules(findings)


def test_width_ok_annotation_suppresses_and_feeds_inventory():
    src = ("import jax.numpy as jnp\n"
           "def flat(ne_pad):\n"
           "    return jnp.arange(ne_pad * ne_pad, dtype=jnp.int32)"
           "  # graftlint: width-ok=test reason\n")
    assert not _lint(src)
    from cuvite_tpu.analysis.callgraph import summarize
    from cuvite_tpu.analysis.engine import SourceFile

    sf = SourceFile(src, path="sab.py", rel="cuvite_tpu/ops/sab.py")
    inv = wc.width_inventory([summarize(sf)])
    assert len(inv) == 1 and inv[0]["reason"] == "test reason"


def test_non_device_path_files_carry_no_sites():
    # serve/ and obs/ hold no slab-extent index arithmetic by scope.
    from cuvite_tpu.analysis.engine import SourceFile

    sf = SourceFile("import jax.numpy as jnp\n"
                    "def f(ne_pad):\n"
                    "    return jnp.arange(ne_pad * ne_pad, "
                    "dtype=jnp.int32)\n",
                    path="d.py", rel="cuvite_tpu/serve/d.py")
    assert wc.width_summary(sf)["sites"] == []


# ---------------------------------------------------------------------------
# Dynamic sabotage: W001/W002 convict seeded overflows.


def test_w001_convicts_narrow_cumsum_over_wide_slab():
    def entry(mask):
        return jnp.cumsum(mask.astype(jnp.int32))

    jaxpr = jax.make_jaxpr(entry)(
        jax.ShapeDtypeStruct(((1 << 31) + 8,), jnp.bool_))
    findings = wa.index_width_findings(jaxpr, "sabotage", 32)
    assert findings and all(f.rule == "W001" for f in findings)
    assert findings[0].path == "<width:sabotage>"


def test_w001_passes_widest_legal_slab():
    from cuvite_tpu.ops.segment import SLAB_NE_MAX

    def entry(mask):
        return jnp.cumsum(mask.astype(jnp.int32))

    jaxpr = jax.make_jaxpr(entry)(
        jax.ShapeDtypeStruct((SLAB_NE_MAX,), jnp.bool_))
    assert not wa.index_width_findings(jaxpr, "ok", 32)


def test_w002_boundary_probes_green_under_code_laws():
    findings, facts = wa.boundary_probes(wa.code_laws())
    assert not findings, "\n".join(f.format() for f in findings)
    assert (1, "int32", 1) in facts["sort_widest_legal"]
    assert any(nk == 2 for nk, _dt, _nd in facts["sort_one_past"])
    assert (1, "int64", 1) in facts["sort_forced_64"]
    assert facts["slab_one_past"] == "raised"
    assert facts["accum"] == {"below": "float32", "at": "ds32",
                              "by_addends": "ds32"}


def test_w002_convicts_when_law_disagrees_with_code():
    # A manifest claiming a 30-bit pack budget makes the real 31-bit
    # packing look one-past — the probe must convict, proving W002 has
    # teeth when predicate and law drift apart.
    laws = dict(wa.code_laws(), pack_bits=30)
    findings, _facts = wa.boundary_probes(laws)
    assert any(f.rule == "W002" for f in findings)


def test_w003_fails_closed_on_missing_manifest(tmp_path):
    findings, _reports = wa.run_width_audit(
        entry_names=[], budget_path=str(tmp_path / "nope.json"),
        probes=False)
    assert _rules(findings) == ["W003"]
    assert "unreadable" in findings[0].message


def test_w003_convicts_drifted_manifest_law():
    manifest = {"version": wa.BUDGET_VERSION,
                "laws": dict(wa.code_laws(), slab_ne_max=1 << 20),
                "max_workload": wc.MAX_WORKLOAD}
    findings = wa.manifest_crosscheck(manifest)
    assert any(f.rule == "W003" and "slab_ne_max" in f.message
               for f in findings)


def test_w003_convicts_crashing_entry(monkeypatch):
    def boom(nv, ne):
        raise RuntimeError("seeded crash")

    monkeypatch.setitem(wa.ENTRIES, "solo_sort_step", (boom, True))
    findings, _ = wa.run_width_audit(
        entry_names=["solo_sort_step"], workloads=["rmat_s28"],
        probes=False)
    assert any(f.rule == "W003" and "seeded crash" in f.message
               for f in findings)


# ---------------------------------------------------------------------------
# Cache discipline: static width facts ride the summary cache
# bit-identically; dynamic W00x results never touch it.


def test_width_summary_rides_cache_warm_equals_cold(tmp_path):
    from cuvite_tpu.analysis.engine import run_paths

    # Lay the file out so its repo-relative rel lands under the
    # device-path prefix the interpreter scopes to.
    src_dir = tmp_path / "cuvite_tpu" / "ops"
    src_dir.mkdir(parents=True)
    src = src_dir / "sab.py"
    src.write_text("import jax.numpy as jnp\n"
                   "def flat(ne_pad):\n"
                   "    return jnp.arange(ne_pad * ne_pad, "
                   "dtype=jnp.int32)\n")
    cache = tmp_path / "cache.json"
    root = str(tmp_path / "cuvite_tpu")
    cold = run_paths([root], cache=str(cache))
    warm = run_paths([root], cache=str(cache))
    assert [f.to_dict() for f in cold] == [f.to_dict() for f in warm]
    assert "R026" in _rules(cold)
    doc = json.loads(cache.read_text())
    summaries = [e.get("summary") for e in doc.get("entries", {}).values()]
    assert any((s or {}).get("width", {}).get("sites")
               for s in summaries), \
        "width facts must ride the tier-2 summary cache"


@pytest.mark.slow
def test_width_audit_never_touches_lint_cache(tmp_path):
    """Tier-2 (slow): pays a full ~9 s dynamic width audit to pin a
    one-time layering invariant (dynamic W00x results never enter the
    lint cache). The audit's tier-1 sibling is
    test_width_audit_green_on_current_tree; the cache's byte-stability
    pins live in the tier-1 cache tests above."""
    from cuvite_tpu.analysis.engine import run_paths

    cache = tmp_path / "cache.json"
    src = tmp_path / "m.py"
    src.write_text("x = 1\n")
    run_paths([str(src)], cache=str(cache))
    before = cache.read_bytes()
    findings, _ = wa.run_width_audit(
        entry_names=["solo_sort_step"], workloads=["rmat_s28"],
        probes=False)
    assert not findings
    assert cache.read_bytes() == before, \
        "dynamic W00x results must never enter the lint cache"


# ---------------------------------------------------------------------------
# SARIF + CLI surfaces.


def test_sarif_roundtrip_includes_width_rules():
    from cuvite_tpu.analysis.__main__ import to_sarif

    findings = _lint(
        "import jax.numpy as jnp\n"
        "def flat(ne_pad):\n"
        "    return jnp.arange(ne_pad * ne_pad, dtype=jnp.int32)\n")
    doc = json.loads(json.dumps(to_sarif(findings)))
    rule_ids = {r["id"] for r in doc["runs"][0]["tool"]["driver"]["rules"]}
    assert {"R026", "R027", "R028"} <= rule_ids
    results = doc["runs"][0]["results"]
    assert any(r["ruleId"] == "R026" for r in results)
    assert all(r["partialFingerprints"]["graftlintFingerprint/v1"]
               for r in results)


def test_width_audit_cli_inventory_subprocess():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "width_audit.py"),
         "--inventory", "--json"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    inv = json.loads(out.stdout)
    assert all(e["reason"] for e in inv)
    # The deliberate 32-bit site of this tree is in the closed
    # inventory: the per-vertex n_moved.
    rels = {e["rel"] for e in inv}
    assert "cuvite_tpu/louvain/step.py" in rels


def test_width_audit_cli_write_budget(tmp_path):
    budget = tmp_path / "budget.json"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "width_audit.py"),
         "--write-budget", "--budget", str(budget)],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    doc = json.loads(budget.read_text())
    assert doc["version"] == wa.BUDGET_VERSION
    assert doc["laws"] == wa.code_laws()
    # The regenerated manifest is exactly the checked-in one: the
    # committed artifact cannot drift from the generator.
    committed = json.loads(
        open(os.path.join(REPO, "tools", "width_budget.json")).read())
    assert doc == committed
