#!/bin/sh
# graftlint pre-commit one-liner: the EXACT gate tests/test_analysis.py
# enforces in tier-1 (new high-severity finding anywhere in cuvite_tpu/,
# tools/, or tests/ => exit 1), warm-started from the incremental cache
# (tools/.graftlint_cache.json — bit-identical to a cold run; delete it
# any time).  Extra args pass through, e.g.:
#   tools/lint.sh --fail-on medium        # stricter local run
#   tools/lint.sh --format json|sarif     # machine-readable findings
#   tools/lint.sh --prune-baseline        # drop dead baseline entries
#   tools/lint.sh --changed               # only files touched vs HEAD
#                                         # (+ untracked) — the fast
#                                         # pre-commit loop; a subset
#                                         # run loses the cross-module
#                                         # tier's full context, so run
#                                         # the full gate before pushing
#   tools/lint.sh --sched-smoke           # tier-4 concheck self-check:
#                                         # a small FIXED-seed schedule
#                                         # budget over the daemon
#                                         # scenarios (clean ones must
#                                         # explore clean, the known-bug
#                                         # fixtures must be convicted).
#                                         # CUVITE_SCHED_BUDGET raises
#                                         # the budget; extra args pass
#                                         # through (--scenario, --seed,
#                                         # --format json).  Dynamic
#                                         # results are never cached.
#   tools/lint.sh --mesh-smoke            # tier-5 mesh-audit self-check:
#                                         # the bucketed SPMD step (both
#                                         # exchanges) at two fixed mesh
#                                         # shapes — M001 collective
#                                         # sequences, M002 label
#                                         # neutrality, M003 replication
#                                         # scaling vs tools/
#                                         # replication_budget.json.
#                                         # Extra args pass through
#                                         # (--entries, --shapes,
#                                         # --json).  Dynamic results
#                                         # are never cached; the full
#                                         # audit runs in tier-1.
#   tools/lint.sh --width-smoke           # tier-6 width-audit self-check:
#                                         # the packed-sort slab entries
#                                         # traced at the scale-28 shard
#                                         # shape (zero bytes allocated)
#                                         # + every boundary probe —
#                                         # W001 index-carrying buffer
#                                         # widths, W002 fallback
#                                         # selection at the bit edges,
#                                         # W003 manifest drift vs
#                                         # tools/width_budget.json.
#                                         # Extra args pass through
#                                         # (--entries, --workloads,
#                                         # --json, --inventory).
#                                         # Dynamic results are never
#                                         # cached; the full audit runs
#                                         # in tier-1.
# See ANALYSIS.md for the rule catalogue and suppression/baseline flow.
cd "$(dirname "$0")/.." || exit 2
if [ "$1" = "--width-smoke" ]; then
    shift
    exec python tools/width_audit.py --smoke "$@"
fi
if [ "$1" = "--mesh-smoke" ]; then
    shift
    exec python tools/mesh_audit.py --smoke "$@"
fi
if [ "$1" = "--sched-smoke" ]; then
    shift
    # Forced-CPU like tier-1: the harness stubs the batch runner, but
    # the serve import chain initializes a jax backend.
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" export JAX_PLATFORMS
    exec python -m cuvite_tpu.analysis.concheck \
        --budget "${CUVITE_SCHED_BUDGET:-8}" --seed 0 "$@"
fi
if [ "$1" = "--changed" ]; then
    shift
    # --diff-filter=d: a DELETED file must not reach the linter (its
    # path would fail closed with a high E000 'no Python files').
    changed=$( { git diff --name-only --diff-filter=d HEAD -- \
                     'cuvite_tpu/*.py' 'tools/*.py' 'tests/*.py'; \
                 git ls-files --others --exclude-standard \
                     'cuvite_tpu/*.py' 'tools/*.py' 'tests/*.py'; } \
               | sort -u)
    if [ -z "$changed" ]; then
        echo "graftlint: no changed Python files under the gate paths; ok"
        exit 0
    fi
    # shellcheck disable=SC2086 — word-splitting the file list is the point
    exec python -m cuvite_tpu.analysis $changed \
        --baseline tools/graftlint_baseline.json \
        --cache tools/.graftlint_cache.json "$@"
fi
exec python -m cuvite_tpu.analysis cuvite_tpu tools tests \
    --baseline tools/graftlint_baseline.json \
    --cache tools/.graftlint_cache.json "$@"
