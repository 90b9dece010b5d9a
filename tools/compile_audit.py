"""Compile-budget + jaxpr audit CLI (graftlint tier 3).

Runs the real serving-path entries at ONE representative small slab
class ((4096, 16384) — the floor every tiny graph canonicalizes to) on
CPU, watches what XLA actually compiles (obs/compile_watch.py), and
grades the observed compile set against the checked-in closed manifest
``tools/compile_budget.json``:

  * B001 — a module compiled that matches nothing in the manifest
    (a NEW program appeared on the serving path);
  * B002 — rerunning an entry with different batch CONTENT (same slab
    class, B, engine; only the weights change) compiled anything:
    content has entered a compile key, the exact regression PR 10 could
    only catch by hand measurement;
  * B003 — compile count over the entry's budget;
  * J001/J002/J003 — the traced per-phase jaxprs contain 64-bit ops,
    host callbacks, or in-graph transfers (analysis/jaxpr_audit.py).

Usage:
    python tools/compile_audit.py                 # audit, exit 1 on FAIL
    python tools/compile_audit.py --write-manifest  # regenerate budget
    python tools/compile_audit.py --json            # machine-readable
    python tools/compile_audit.py --entries batched_fused_B2 ...

The audit is deterministic: graph structure is fixed, only weights vary
with the content seed, and everything runs on the forced-CPU 8-virtual-
device backend tier-1 uses (the same programs either way).  The tier-1
test (tests/test_analysis.py) runs the same scenarios in-process, plus
a sabotage fixture asserting B002 actually fires when content is
threaded into a static argument.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

MANIFEST = os.path.join(REPO_ROOT, "tools", "compile_budget.json")

# Tier-1's backend shape, replicated for standalone runs: 8 virtual CPU
# devices so the batch-axis mesh (and therefore the compiled module
# set) matches what the in-suite audit and the manifest record.
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms",
                  os.environ.get("JAX_PLATFORMS", "cpu"))

from cuvite_tpu.analysis.jaxpr_audit import (  # noqa: E402
    audit_entry,
    audit_jaxprs,
    load_manifest,
    tiny_graphs,
    write_manifest,
)

MAX_PHASES = 2  # enough to cover the coarse-class programs


def _run_batched(engine):
    def run(seed):
        from cuvite_tpu.louvain.batched import cluster_many

        cluster_many(tiny_graphs(b=2, content_seed=seed),
                     threshold=1.0e-6, max_phases=MAX_PHASES,
                     engine=engine)
    return run


def _run_solo(engine):
    def run(seed):
        from cuvite_tpu.louvain.driver import louvain_phases

        # Phase 0 only: the per-graph driver's COARSE classes are
        # content-dependent by design (maybe_shrink_to_class follows the
        # coarsened sizes), so a multi-phase solo run recompiles
        # legitimately when content changes; the batched entries cover
        # the multi-phase budget instead.
        louvain_phases(tiny_graphs(b=1, content_seed=seed)[0],
                       engine=engine, max_phases=1)
    return run


def _run_serve(seed):
    from cuvite_tpu.serve.queue import LouvainServer, ServeConfig

    server = LouvainServer(ServeConfig(
        b_max=2, linger_s=0.0, engine="bucketed", max_phases=MAX_PHASES))
    for g in tiny_graphs(b=2, content_seed=seed):
        server.submit(g)
    server.step(force=True)


def _run_subrow(seed):
    """Packed sub-row batch (ISSUE 20): three tiny small-class graphs
    merged as fenced sub-rows of (8192, 32768) rows.  The compile key
    is (row class, B, n_sub, engine) — batch CONTENT and sub-row
    OCCUPANCY are runtime operands, so the content-seed rerun must
    compile nothing (B002 otherwise)."""
    from cuvite_tpu.core.batch import subrow_layout_for
    from cuvite_tpu.louvain.batched import cluster_packed

    layout = subrow_layout_for((4096, 16384), (8192, 32768))
    cluster_packed(tiny_graphs(b=3, content_seed=seed), layout,
                   threshold=1.0e-6, max_phases=MAX_PHASES)


# Entry registry: name -> run(content_seed).  Names match the manifest.
ENTRIES = {
    "solo_fused_sort": _run_solo("sort"),
    "solo_bucketed": _run_solo("auto"),
    "batched_fused_B2": _run_batched("fused"),
    "batched_bucketed_B2": _run_batched("bucketed"),
    "serve_pack_bucketed_B2": _run_serve,
    "packed_subrow_B2": _run_subrow,
}


def run_audit(entry_names=None, manifest_path: str = MANIFEST,
              with_jaxprs: bool = True):
    """(results, jaxpr_findings).  Shared by the CLI and the tier-1
    test — one implementation, one behavior."""
    try:
        manifest = load_manifest(manifest_path)
    except (OSError, ValueError):
        manifest = {"entries": {}}
    # Match against the UNION of every entry's modules: which entry a
    # shared program's compile lands on depends on jit-cache warmth and
    # run order (audited alone, the serve path compiles the batched
    # entries' programs itself).  Closedness holds at manifest level.
    union = sorted({p for e in manifest["entries"].values()
                    for p in e.get("modules", ())})
    results = []
    for name in (entry_names or ENTRIES):
        results.append(audit_entry(
            name, ENTRIES[name], manifest["entries"].get(name),
            extra_patterns=union))
    jaxpr_findings = audit_jaxprs() if with_jaxprs else []
    return results, jaxpr_findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python tools/compile_audit.py",
        description="cuvite_tpu compile-budget + jaxpr audit (tier 3)")
    ap.add_argument("--entries", nargs="*", default=None,
                    choices=sorted(ENTRIES), help="subset of entries")
    ap.add_argument("--manifest", default=MANIFEST)
    ap.add_argument("--write-manifest", action="store_true",
                    help="record the observed compile sets as the new "
                         "closed manifest (review the diff!)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if args.write_manifest:
        entries = {}
        for name in (args.entries or ENTRIES):
            res = audit_entry(name, ENTRIES[name], manifest_entry={
                "modules": ["*"], "content_independent": False})
            mods = sorted(set(res.observed))
            entries[name] = {
                "modules": mods,
                # slack for jax-version drift in helper-jit names
                "max_compiles": len(res.observed) + 4,
                "content_independent": not res.recompiled,
            }
            print(f"{name}: {len(res.observed)} compile(s), "
                  f"{len(res.recompiled)} on content change")
        env = {
            "platform": jax.default_backend(),
            "device_count": jax.device_count(),
            "max_phases": MAX_PHASES,
            "slab_class": [4096, 16384],
        }
        write_manifest(args.manifest, entries, env)
        print(f"wrote {args.manifest}")
        return 0

    results, jaxpr_findings = run_audit(args.entries, args.manifest)
    findings = [f for r in results for f in r.findings] + jaxpr_findings
    if args.json:
        print(json.dumps({
            "entries": [{
                "entry": r.entry, "observed": r.observed,
                "recompiled": r.recompiled,
                "findings": [f.to_dict() for f in r.findings],
            } for r in results],
            "jaxpr_findings": [f.to_dict() for f in jaxpr_findings],
            "ok": not findings,
        }, indent=2))
    else:
        for r in results:
            state = "ok" if r.ok else "FAIL"
            print(f"{r.entry}: {len(r.observed)} compile(s), "
                  f"{len(r.recompiled)} on content change [{state}]")
        for f in findings:
            print(f.format())
        print(f"compile_audit: {len(findings)} finding(s); "
              f"{'FAIL' if findings else 'ok'}")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
