"""Width audit CLI (graftlint tier 6, dynamic half).

Traces the real device-path entries — the solo sort/bucketed/fused
phase programs, the batched execute, and the device coarsen+coalesce —
at the Friendster-class and R-MAT scale-28 slab shapes with ZERO
device bytes allocated (everything stages abstractly; a live-buffer
spy pins the invariant), and grades:

  * W001 — index-carrying jaxpr buffers (iota / cumsum run ids) wide
    enough for the extent they index at that shape;
  * W002 — every eligibility predicate actually selecting its
    fallback at the boundary: the packed int32 sort at
    kbits+sbits == 31 vs the lexicographic comparator one past (and
    the int64 pack under forced x64), the SLAB_NE_MAX raise-guard,
    the DS_MIN_TOTAL_WEIGHT ds32 cutover;
  * W003 — audit integrity: crashed entries, a budget manifest that
    drifted from the code constants or the registry's declared max
    workload, or a nonzero live-buffer delta all fail CLOSED.

Usage:
    python tools/width_audit.py                   # full audit, exit 1 on FAIL
    python tools/width_audit.py --smoke           # fast self-check
    python tools/width_audit.py --entries solo_sort_step ...
    python tools/width_audit.py --workloads rmat_s28
    python tools/width_audit.py --json            # machine-readable
    python tools/width_audit.py --inventory       # width-ok annotated sites
    python tools/width_audit.py --out FILE.json   # checkpoint the report
    python tools/width_audit.py --write-budget    # regenerate the manifest

Dynamic results are never cached; the audit re-runs the traces every
time.  The tier-1 test (tests/test_widthcheck.py) runs the same audit
in-process plus sabotage fixtures proving R026-R028/W001-W002 convict
seeded overflows.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

BUDGET = os.path.join(REPO_ROOT, "tools", "width_budget.json")

import jax  # noqa: E402

jax.config.update("jax_platforms",
                  os.environ.get("JAX_PLATFORMS", "cpu"))

from cuvite_tpu.analysis.widthaudit import (  # noqa: E402
    ENTRIES,
    audit_workloads,
    code_laws,
    run_width_audit,
    write_budget,
)

# --smoke: the packed-sort slab entry plus the boundary probes at ONE
# workload — the fast pre-commit self-check lint.sh --width-smoke
# runs (the probes carry most of W002's teeth; the full two-workload
# sweep runs in tier-1).
SMOKE_ENTRIES = ("solo_sort_step", "coarsen_coalesce")
SMOKE_WORKLOADS = ("rmat_s28",)


def _inventory() -> list:
    """The width-ok inventory, rebuilt from the live tree (static
    tier; no jax involved)."""
    from cuvite_tpu.analysis.callgraph import summarize
    from cuvite_tpu.analysis.engine import SourceFile, iter_py_files
    from cuvite_tpu.analysis.widthcheck import width_inventory

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
    return width_inventory(summaries)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python tools/width_audit.py",
        description="cuvite_tpu index-width audit (tier 6, W001-W003)")
    ap.add_argument("--entries", nargs="*", default=None,
                    choices=sorted(ENTRIES), help="subset of entries")
    ap.add_argument("--workloads", nargs="*", default=None,
                    metavar="NAME", help="subset of workloads "
                    "(default: " + " ".join(sorted(audit_workloads()))
                    + ")")
    ap.add_argument("--smoke", action="store_true",
                    help="fast self-check "
                         f"({', '.join(SMOKE_ENTRIES)} at "
                         f"{'/'.join(SMOKE_WORKLOADS)} + all probes)")
    ap.add_argument("--budget", default=BUDGET)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="write the JSON report to FILE (per-workload "
                         "sort facts + findings)")
    ap.add_argument("--inventory", action="store_true",
                    help="print the closed width-ok inventory and "
                         "exit (static tier only)")
    ap.add_argument("--write-budget", action="store_true",
                    help="regenerate the width manifest from the code "
                         "constants, the registry's max workload, and "
                         "the derived certification shapes — review "
                         "the diff before committing")
    args = ap.parse_args(argv)

    if args.inventory:
        inv = _inventory()
        if args.json:
            print(json.dumps(inv, indent=2))
        else:
            for ent in inv:
                print(f"{ent['rel']}:{ent['line']}: {ent['kind']} "
                      f"[{ent['bound']}] — {ent['reason']}")
            print(f"width_audit: {len(inv)} justified 32-bit site(s) "
                  "in the inventory")
        return 0

    if args.write_budget:
        from cuvite_tpu.workloads import registry

        write_budget(args.budget, {
            "laws": code_laws(),
            "max_workload": registry.max_workload(),
            "workloads": audit_workloads(),
        })
        print(f"width_audit: wrote {args.budget} (laws + max workload "
              "+ certification shapes; review the diff)")
        return 0

    # nargs="*" admits a bare `--entries` (an empty $ENTRIES in a
    # script): treat it as "all entries", never as a vacuous zero-entry
    # audit that greens without auditing anything.
    entries = args.entries or None
    workloads = args.workloads or None
    if args.smoke:
        entries = entries or list(SMOKE_ENTRIES)
        workloads = workloads or list(SMOKE_WORKLOADS)

    findings, reports = run_width_audit(entries, workloads=workloads,
                                        budget_path=args.budget)
    doc = {
        "platform": jax.default_backend(),
        "reports": reports,
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
        spy = reports.get("spy", {})
        for wname in sorted(k for k in reports
                            if k not in ("probes", "spy")):
            per = reports[wname]
            state = "ok" if not any(
                f.path == f"<width:{e}>" for e in per
                for f in findings) else "FAIL"
            ents = ", ".join(sorted(per))
            print(f"{wname}: entries [{ents}] [{state}]")
        print(f"width_audit: spy delta "
              f"{spy.get('delta_bytes', '?')} byte(s)")
        for f in findings:
            print(f.format())
        print(f"width_audit: {len(findings)} finding(s); "
              f"{'FAIL' if findings else 'ok'}")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
