"""Mesh audit CLI (graftlint tier 5, dynamic half).

Runs the real sharded entries — the per-graph bucketed SPMD step under
the replicated, sparse, auto-cutover, and two-level (hybrid dcn/ici
mesh) exchanges, and the batched fused/bucketed phase programs —
across the virtual mesh shapes {8x1, 4x2, 2x4} of tier-1's forced-CPU
8-device pool (the two-level entry reads each shape as its (dcn, ici)
factorization), and grades:

  * M001 — per-shard collective sequences: extracted from the traced
    jaxprs; a cond whose branches issue different collective
    subsequences, or a sequence that changes structure across mesh
    shapes, is a conviction;
  * M002 — labels + modularity bit-identical across every mesh shape
    (the generalized mesh-neutrality gate);
  * M003 — per-device HBM-ledger bytes vs the per-category scaling law
    declared in ``tools/replication_budget.json`` (the closed
    replication inventory: 'sharded' must shrink ~1/S, 'replicated'
    must be listed);
  * M000 — audit infrastructure failures (an entry crashed, the budget
    manifest is unreadable) fail CLOSED.

Usage:
    python tools/mesh_audit.py                    # full audit, exit 1 on FAIL
    python tools/mesh_audit.py --smoke            # fixed-shape fast self-check
    python tools/mesh_audit.py --entries bucketed_sparse ...
    python tools/mesh_audit.py --shapes 8x1 4x2   # subset of shapes
    python tools/mesh_audit.py --json             # machine-readable
    python tools/mesh_audit.py --inventory        # R025 replicated-ok sites
    python tools/mesh_audit.py --out FILE.json    # checkpoint the report

Dynamic results are never cached; the audit re-runs the entries every
time.  The tier-1 test (tests/test_meshcheck.py) runs the same audit
in-process plus sabotage fixtures proving M001/M003 convict seeded
bugs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

BUDGET = os.path.join(REPO_ROOT, "tools", "replication_budget.json")

# Tier-1's backend shape, replicated for standalone runs (the
# compile_audit precedent): the mesh shapes need 8 devices.  On a real
# TPU slice the flag is a no-op — the chips are real.
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms",
                  os.environ.get("JAX_PLATFORMS", "cpu"))

from cuvite_tpu.analysis.meshcheck import (  # noqa: E402
    ENTRIES,
    MESH_SHAPES,
    load_budget,
    run_mesh_audit,
    write_budget,
)

# --smoke: one exchange per engine family at a fixed pair of shapes —
# the fast pre-commit self-check lint.sh --mesh-smoke runs (still
# cross-shape, so M001/M002/M003 all have teeth; the full gate runs in
# tier-1).
SMOKE_ENTRIES = ("bucketed_replicated", "bucketed_sparse")
SMOKE_SHAPES = ((4, 2), (2, 4))


def _parse_shapes(tokens):
    shapes = []
    for t in tokens:
        a, _, b = t.partition("x")
        shapes.append((int(a), int(b or 1)))
    return tuple(shapes)


def _inventory() -> list:
    """The R025 replicated-ok inventory, rebuilt from the live tree
    (static tier; no jax involved)."""
    from cuvite_tpu.analysis.callgraph import summarize
    from cuvite_tpu.analysis.engine import SourceFile, iter_py_files
    from cuvite_tpu.analysis.meshspec import replicated_inventory

    summaries = []
    for path in iter_py_files([os.path.join(REPO_ROOT, "cuvite_tpu"),
                               os.path.join(REPO_ROOT, "tools")]):
        rel = os.path.relpath(path, REPO_ROOT).replace(os.sep, "/")
        try:
            with open(path, encoding="utf-8") as fh:
                summaries.append(summarize(SourceFile(fh.read(),
                                                      path=path, rel=rel)))
        except (OSError, SyntaxError, ValueError):
            continue
    return replicated_inventory(summaries)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python tools/mesh_audit.py",
        description="cuvite_tpu SPMD mesh audit (tier 5, M001-M003)")
    ap.add_argument("--entries", nargs="*", default=None,
                    choices=sorted(ENTRIES), help="subset of entries")
    ap.add_argument("--shapes", nargs="*", default=None,
                    metavar="SxT", help="mesh shapes (default: "
                    + " ".join(f"{a}x{b}" for a, b in MESH_SHAPES))
    ap.add_argument("--smoke", action="store_true",
                    help="fast fixed-shape self-check "
                         f"({', '.join(SMOKE_ENTRIES)} at "
                         f"{'/'.join(f'{a}x{b}' for a, b in SMOKE_SHAPES)})")
    ap.add_argument("--budget", default=BUDGET)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="write the JSON report to FILE (per-shape "
                         "ledger rows + findings)")
    ap.add_argument("--inventory", action="store_true",
                    help="print the R025 replicated-ok inventory and "
                         "exit (static tier only)")
    ap.add_argument("--write-budget", action="store_true",
                    help="regenerate the scaling-law manifest from the "
                         "observed ledger categories (existing entries "
                         "kept; NEW categories default to law='sharded' "
                         "— the failing-closed default — edit the "
                         "reasons before committing)")
    args = ap.parse_args(argv)

    if args.inventory:
        inv = _inventory()
        if args.json:
            print(json.dumps(inv, indent=2))
        else:
            for ent in inv:
                print(f"{ent['rel']}:{ent['line']}: {ent['call']} "
                      f"[{ent['size']}] [scope={ent['scope']}] — "
                      f"{ent['reason']}")
            n_global = sum(1 for ent in inv if ent["scope"] == "global")
            print(f"mesh_audit: {len(inv)} justified replicated "
                  f"buffer(s) in the inventory; {n_global} with global "
                  "scope (two-level contract: 0)")
        return 0

    # nargs="*" admits a bare `--entries` (e.g. an empty $ENTRIES in a
    # script): treat it as "all entries", never as a vacuous zero-entry
    # audit that greens without auditing anything.
    entries = args.entries or None
    shapes = _parse_shapes(args.shapes) if args.shapes else None
    if args.smoke:
        entries = entries or list(SMOKE_ENTRIES)
        shapes = shapes or SMOKE_SHAPES
    shapes = shapes or MESH_SHAPES

    if args.write_budget:
        _findings, reports = run_mesh_audit(entries, shapes=shapes,
                                            budget_path=args.budget)
        try:
            cats = dict(load_budget(args.budget).get("categories", {}))
        except (OSError, ValueError):
            cats = {}
        observed = sorted({cat for by_shape in reports.values()
                           for rep in by_shape.values()
                           for cat in rep.categories})
        fresh = [cat for cat in observed if cat not in cats]
        for cat in fresh:
            cats[cat] = {
                "law": "sharded",
                "reason": "autogenerated by --write-budget — declare "
                          "the law (sharded/replicated) deliberately",
            }
        write_budget(args.budget, cats, {
            "device_count": jax.device_count(),
            "platform": jax.default_backend(),
            "shapes": [f"{a}x{b}" for a, b in shapes],
        })
        print(f"mesh_audit: wrote {len(cats)} categories to "
              f"{args.budget} ({len(fresh)} new, defaulted to "
              "law='sharded'; edit the reasons before committing)")
        return 0

    findings, reports = run_mesh_audit(entries, shapes=shapes,
                                       budget_path=args.budget)
    doc = {
        "platform": jax.default_backend(),
        "device_count": jax.device_count(),
        "shapes": [f"{a}x{b}" for a, b in shapes],
        "entries": {
            name: {
                tag: {
                    "devices": rep.devices,
                    "n_results": len(rep.labels),
                    "collectives": len(rep.seq),
                    "ledger": rep.categories,
                }
                for tag, rep in by_shape.items()
            }
            for name, by_shape in reports.items()
        },
        "findings": [f.to_dict() for f in findings],
        "ok": not findings,
    }
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
            fh.write("\n")
    if args.json:
        print(json.dumps(doc, indent=2, sort_keys=True))
    else:
        for name, by_shape in reports.items():
            tags = ", ".join(sorted(by_shape))
            state = "ok" if not any(
                f.path == f"<mesh:{name}>" for f in findings) else "FAIL"
            print(f"{name}: shapes [{tags}] [{state}]")
        for f in findings:
            print(f.format())
        print(f"mesh_audit: {len(findings)} finding(s); "
              f"{'FAIL' if findings else 'ok'}")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
