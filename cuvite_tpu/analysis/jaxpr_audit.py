"""Tier 3 — jaxpr lint + compile-budget audit (the dynamic tier).

Tiers 1/2 read source; this tier inspects the PROGRAMS the source
builds, because two invariants the serving stack rests on are invisible
to any AST walk:

  * **jaxpr hygiene** — the traced per-phase programs must contain no
    64-bit ops (the 32-bit device contract, R003's runtime twin), no
    ``pure_callback``/``io_callback`` escapes (a host callback inside
    the phase loop is a hidden per-iteration sync), and no in-graph
    ``device_put`` transfers (placement belongs to the driver's one
    upload per batch).  :func:`lint_jaxpr` walks a ClosedJaxpr
    (sub-jaxprs included) and reports J001/J002/J003 findings.

  * **compile budget** — "batch content never enters the compile key"
    (PR 10's measured contract) and "one compiled program per (class,
    B, engine)" stop being per-PR measurements: :func:`audit_entry`
    runs a real entry twice under the existing
    :class:`~cuvite_tpu.obs.compile_watch.CompileWatcher` — same slab
    class and B, different *content* — and reports B001 (a compiled
    module outside the closed manifest), B002 (the second run compiled
    ANYTHING: content reached a compile key), and B003 (compile count
    over the entry's budget).  ``tools/compile_audit.py`` is the CLI;
    ``tools/compile_budget.json`` is the checked-in manifest of
    (entry, slab class, B, engine) -> expected module set.

Everything jax-touching imports lazily: ``python -m cuvite_tpu.analysis``
(tiers 1/2) must keep running in environments with no jax at all.

Finding rule ids here (J*/B*) are deliberately OUTSIDE the R-rule
registry: they anchor on programs/entries, not source lines, and are
gated by tests/test_analysis.py + the audit CLI rather than the source
linter.  Severity follows the same vocabulary ("high" fails).
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np

from cuvite_tpu.analysis.engine import Finding

# Dtypes that must not appear in a serving-path jaxpr (the 32-bit
# device contract; jax_enable_x64 oracle runs are out of audit scope).
WIDE_DTYPES = {"float64", "int64", "uint64", "complex128"}

# Primitive-name substrings that mark a host callback escape.
CALLBACK_PRIM_MARKERS = ("callback", "outside_call", "infeed", "outfeed")

# Primitives that move data between host and device inside the traced
# program (placement belongs to the driver, once per batch).
TRANSFER_PRIMS = {"device_put", "copy_to_host_async"}

MANIFEST_VERSION = 1


def _iter_eqns(jaxpr):
    """Every eqn of a (Closed)Jaxpr, recursing into sub-jaxprs (pjit
    bodies, while/cond/scan branches, shard_map bodies, ...)."""
    core_jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in core_jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_eqns(sub)


def _sub_jaxprs(value):
    out = []
    stack = [value]
    while stack:
        v = stack.pop()
        if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
            out.append(v)
        elif isinstance(v, (tuple, list)):
            stack.extend(v)
    return out


def lint_jaxpr(jaxpr, entry: str, allow: tuple = ()) -> list:
    """J001/J002/J003 findings for one traced program.  ``allow`` is a
    tuple of rule ids to skip (a manifest entry can grandfather a
    deliberate callback, say).  Findings anchor on the pseudo-path
    ``<jaxpr:ENTRY>`` with the primitive name as the snippet."""
    findings = []
    seen = set()

    def add(rule, prim, msg):
        if rule in allow:
            return
        key = (rule, prim)
        if key in seen:  # one finding per (rule, primitive) per entry
            return
        seen.add(key)
        findings.append(Finding(
            rule=rule, severity="high", path=f"<jaxpr:{entry}>", line=0,
            message=msg, snippet=prim))

    for eqn in _iter_eqns(jaxpr):
        prim = eqn.primitive.name
        if any(m in prim for m in CALLBACK_PRIM_MARKERS):
            add("J002", prim,
                f"host callback primitive '{prim}' inside the traced "
                f"program '{entry}': a hidden device->host round trip "
                "per execution (and a donation/buffer hazard under "
                "shard_map); keep host work outside the program")
        if prim in TRANSFER_PRIMS:
            add("J003", prim,
                f"'{prim}' inside the traced program '{entry}': "
                "host/device placement belongs to the driver (one "
                "upload per packed batch), not inside the compiled "
                "program")
        for var in list(eqn.outvars) + list(eqn.invars):
            aval = getattr(var, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and str(dt) in WIDE_DTYPES:
                add("J001", f"{prim}:{dt}",
                    f"64-bit dtype {dt} flows through '{prim}' in the "
                    f"traced program '{entry}': the device path is "
                    "32-bit by contract (graftlint R003's runtime "
                    "twin) — packed keys/ids corrupt silently without "
                    "x64, memory doubles with it")
                break
    return findings


# ---------------------------------------------------------------------------
# Representative serving-class workload (host-side, deterministic).


def tiny_graphs(b: int = 2, nv: int = 256, ne: int = 1024,
                content_seed: int = 1) -> list:
    """``b`` same-structure graphs at the representative small slab
    class (everything below the MIN_NV_PAD/MIN_NE_PAD floors pads to
    (4096, 16384)).  The edge STRUCTURE is fixed — so bucket plans and
    slab classes cannot drift between seeds — and only the weights vary
    with ``content_seed``: exactly the "batch content" PR 10's compile
    contract pins out of the compile key."""
    from cuvite_tpu.core.graph import Graph

    rng = np.random.default_rng(12345)  # structure: seed-INDEPENDENT
    graphs = []
    for j in range(b):
        src = np.concatenate([np.arange(nv), rng.integers(0, nv, ne - nv)])
        dst = np.concatenate([(np.arange(nv) + 1) % nv,
                              rng.integers(0, nv, ne - nv)])
        keep = src != dst
        wrng = np.random.default_rng(100_000 * (j + 1) + content_seed)
        w = wrng.uniform(0.5, 2.0, int(keep.sum()))
        graphs.append(Graph.from_edges(
            nv, src[keep].astype(np.int64), dst[keep].astype(np.int64),
            weights=w))
    return graphs


# ---------------------------------------------------------------------------
# Jaxpr tracing of the real batched-phase programs.


def trace_phase_jaxprs(b: int = 2, nv: int = 256, ne: int = 1024,
                       mesh=None, programs=None) -> dict:
    """{name: ClosedJaxpr} for the real batched per-phase programs at
    the representative class — the fused body, the bucketed phase-0
    body, and the coarse-class shrink.  Arg construction mirrors
    ``run_batched``'s upload block (host numpy stands in for the device
    placement; shapes and dtypes are identical).  ``mesh`` (a 1-D
    batch-axis Mesh) traces the SHARDED program the tier-5 mesh audit
    inspects — the shard_map body's collective sequence then appears in
    the jaxpr exactly as the compiled entry issues it.  ``programs``
    restricts to a subset of the three names (the mesh audit consumes
    one per entry; the bucket-plan build for an untraced program is
    pure waste)."""
    import jax

    from cuvite_tpu.core.batch import batch_bucket_plans, batch_slabs
    from cuvite_tpu.louvain.batched import (
        MAX_TOTAL_ITERATIONS,
        _batch_accum_name,
        _coarse_class,
        _get_batched_phase,
        _shrink_batch,
    )

    batch = batch_slabs(tiny_graphs(b=b, nv=nv, ne=ne))
    nv_pad = batch.nv_pad
    B = batch.b_pad
    wdt = np.dtype(np.float32)
    adt = _batch_accum_name(batch)
    comm_all = np.broadcast_to(
        np.arange(nv_pad, dtype=np.int32)[None, :], (B, nv_pad)).copy()
    prev = np.full((B,), -1.0, dtype=wdt)
    slab_args = (batch.src, batch.dst, batch.w, comm_all,
                 batch.real_mask, prev, batch.row_valid, batch.constant,
                 np.asarray(1.0e-6, dtype=wdt))

    want = set(programs) if programs is not None else {
        "batched_fused_phase", "batched_bucketed_phase0",
        "batched_coarse_shrink"}
    out = {}
    if "batched_fused_phase" in want:
        fused = _get_batched_phase(mesh, nv_pad, adt,
                                   MAX_TOTAL_ITERATIONS)
        out["batched_fused_phase"] = jax.make_jaxpr(fused)(*slab_args)

    if "batched_bucketed_phase0" in want:
        bplan = batch_bucket_plans(batch)
        plan_args = (
            tuple((v.astype(np.int32), d, ww)
                  for v, d, ww in bplan.buckets),
            tuple(bplan.heavy),
            bplan.self_loop,
            bplan.perm,
        )
        bucketed = _get_batched_phase(mesh, nv_pad, adt,
                                      MAX_TOTAL_ITERATIONS,
                                      engine="bucketed",
                                      n_buckets=len(bplan.buckets))
        out["batched_bucketed_phase0"] = jax.make_jaxpr(bucketed)(
            *plan_args, *slab_args)

    if "batched_coarse_shrink" in want:
        cnv, cne = _coarse_class(nv_pad, batch.ne_pad)
        out["batched_coarse_shrink"] = jax.make_jaxpr(
            lambda s, d, w, m: _shrink_batch(s, d, w, m, cnv=cnv,
                                             cne=cne))(
            batch.src, batch.dst, batch.w, batch.real_mask)
    return out


def audit_jaxprs(allow: dict | None = None, **kw) -> list:
    """Trace + lint every serving-path program; ``allow`` maps entry
    name -> tuple of J-rule ids to skip."""
    allow = allow or {}
    findings = []
    for name, jaxpr in trace_phase_jaxprs(**kw).items():
        findings.extend(lint_jaxpr(jaxpr, name,
                                   allow=tuple(allow.get(name, ()))))
    return findings


# ---------------------------------------------------------------------------
# Compile-budget audit.


@dataclasses.dataclass
class AuditResult:
    """One entry's audit: what compiled, what the manifest thought,
    and whether content leaked into a compile key."""

    entry: str
    observed: list          # modules compiled by the first run
    recompiled: list        # modules compiled by the content-changed run
    findings: list          # B001/B002/B003 Finding objects

    @property
    def ok(self) -> bool:
        return not self.findings


def observed_modules(watcher) -> list:
    """Module names a CompileWatcher saw (completed or in flight)."""
    return [e["module"] for e in watcher.events]


def _match(module: str, patterns) -> bool:
    return any(p in module for p in patterns)


def audit_entry(entry: str, run, manifest_entry: dict | None,
                seeds=(1, 2), extra_patterns=()) -> AuditResult:
    """Run ``run(content_seed)`` twice under the compile watcher and
    grade it against one manifest entry (see tools/compile_budget.json;
    None = entry missing from the manifest, which fails closed).

    The first run may compile (cold) or not (warm process): the audit
    requires observed ⊆ the manifest's module patterns and count <=
    ``max_compiles``.  ``extra_patterns`` widens the match set — the
    CLI passes the UNION of every manifest entry's modules, because
    per-entry attribution depends on jit-cache warmth and entry order
    (the serve path compiles nothing after the batched entries ran, but
    compiles THEIR modules when audited alone); the closed-set property
    lives at the manifest level, not per entry.  The second run changes
    ONLY content (same slab class, B, engine): with
    ``content_independent`` set (the default), ANY compile it triggers
    is a B002 — content reached a compile key.
    """
    from cuvite_tpu.obs.compile_watch import CompileWatcher

    with CompileWatcher() as w1:
        run(seeds[0])
    with CompileWatcher() as w2:
        run(seeds[1])
    observed = observed_modules(w1)
    recompiled = observed_modules(w2)
    findings = []
    if manifest_entry is None:
        findings.append(Finding(
            rule="B001", severity="high", path=f"<compile:{entry}>",
            line=0, snippet="",
            message=f"entry '{entry}' is not in the compile-budget "
                    "manifest (tools/compile_budget.json): the expected "
                    "compile set is CLOSED — add the entry deliberately "
                    "via tools/compile_audit.py --write-manifest"))
        return AuditResult(entry, observed, recompiled, findings)
    patterns = list(manifest_entry.get("modules", [])) \
        + list(extra_patterns)
    for mod in observed:
        if not _match(mod, patterns):
            findings.append(Finding(
                rule="B001", severity="high", path=f"<compile:{entry}>",
                line=0, snippet=mod,
                message=f"'{entry}' compiled module '{mod}' which "
                        "matches nothing in the manifest: a NEW compiled "
                        "program appeared on the serving path — extend "
                        "the manifest deliberately (--write-manifest) "
                        "or find what stopped reusing its program"))
    if manifest_entry.get("content_independent", True) and recompiled:
        findings.append(Finding(
            rule="B002", severity="high", path=f"<compile:{entry}>",
            line=0, snippet=", ".join(sorted(set(recompiled))[:4]),
            message=f"'{entry}' recompiled {len(recompiled)} module(s) "
                    "when only batch CONTENT changed (same class, B, "
                    "engine): content has entered a compile key — the "
                    "amortization contract (one program per class/B/"
                    "engine; weights pinned f32) is broken"))
    max_c = manifest_entry.get("max_compiles")
    if max_c is not None and len(observed) > max_c:
        findings.append(Finding(
            rule="B003", severity="high", path=f"<compile:{entry}>",
            line=0, snippet=str(len(observed)),
            message=f"'{entry}' compiled {len(observed)} modules, over "
                    f"the manifest budget of {max_c}: compile-cache "
                    "bloat (or a per-shape/per-value recompile) crept "
                    "in"))
    return AuditResult(entry, observed, recompiled, findings)


def load_manifest(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        data = json.load(fh)
    if data.get("version") != MANIFEST_VERSION:
        raise ValueError(f"compile budget manifest {path!r}: unsupported "
                         f"version {data.get('version')!r}")
    return data


def write_manifest(path: str, entries: dict, env: dict) -> None:
    with open(path, "w", encoding="utf-8") as fh:
        json.dump({"version": MANIFEST_VERSION, "env": env,
                   "entries": entries}, fh, indent=2, sort_keys=True)
        fh.write("\n")
