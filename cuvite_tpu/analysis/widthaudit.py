"""Tier 6 (dynamic half) — the width audit (W001-W003).

The static half (analysis/widthcheck.py: R026-R028) bounds index
arithmetic symbolically; this module traces the REAL device-path
entries — the solo sort/bucketed/fused phase programs, the batched
execute, and the device coarsen+coalesce — at Friendster-class and
R-MAT scale-28 slab shapes via ``jax.make_jaxpr``/``jax.eval_shape``
with ZERO device bytes allocated (every program stages abstractly
under omnistaging; a live-buffer spy pins the invariant), and grades
three properties the AST walk cannot:

  * **W001 — index-carrying buffer width.**  Every ``iota`` /
    ``cumsum``-class equation in the traced jaxprs whose output is an
    integer buffer must be wide enough for the extent it indexes: an
    int32 run-id cumsum over a 2^32-row slab WILL wrap (wrong labels,
    not a crash).  The capacity law (``index_bits``) comes from
    ``tools/width_budget.json``.

  * **W002 — fallbacks actually selected at the boundary.**  Each
    eligibility predicate is probed at its widest-legal shape, one
    step past, and (for the packed sort) under forced x64:

      - the packed single-key int32 sort at ``kbits+sbits == 31`` and
        the lexicographic two-key fallback at ``== 32`` (the
        segment.py contract), with the int64 single-key under
        ``jax_enable_x64``;
      - the ``SLAB_NE_MAX`` raise-guard actually raising one step past
        the ceiling (fail-loud, never wrap);
      - ``_accum_name`` switching to ds32 exactly at
        ``DS_MIN_TOTAL_WEIGHT``.

    Additionally, any traced entry at an ineligible workload
    (``kbits+sbits > 31``) that still contains an int32 single-key
    sort is a conviction — the fallback was NOT selected.

  * **W003 — audit integrity (the M000 precedent).**  A crashing
    entry, an unreadable/mismatched budget manifest (its laws must
    equal the code constants and the registry's declared max
    workload), or a nonzero live-buffer delta after tracing each
    FAILS CLOSED as a finding, never as a silent skip.

Dynamic results are NEVER cached (the concheck/meshcheck precedent):
findings anchor on ``<width:entry>`` pseudo-paths outside the lint
cache.  ``tools/width_audit.py`` is the CLI; tests/test_widthcheck.py
runs the same audit in-process.
"""

from __future__ import annotations

import gc
import json
import os

import numpy as np

from cuvite_tpu.analysis.engine import Finding
from cuvite_tpu.analysis.widthcheck import INT32_MAX, MAX_WORKLOAD

BUDGET_VERSION = 1

DEFAULT_BUDGET_REL = os.path.join("tools", "width_budget.json")

# The fixed class of the batched entry: batched serving multiplexes
# B tenants of modest graphs.
BATCHED_NV = 1 << 12
BATCHED_NE = 1 << 14

# Jaxpr primitives whose integer outputs carry INDICES of their
# operated extent (run ids, positions, slot numbers).  reduce_sum is
# deliberately absent: its addends are unbounded from the jaxpr alone
# and the static tier (R028) already partitions that class.
_INDEX_PRIMS = ("iota", "cumsum", "cummax", "cummin")


def _wfind(rule: str, entry: str, message: str,
           snippet: str = "") -> Finding:
    return Finding(rule=rule, severity="high", path=f"<width:{entry}>",
                   line=0, message=message, snippet=snippet)


def live_device_bytes() -> int:
    """Total bytes of live device buffers — the spy the zero-allocation
    pin reads before and after the trace sweep."""
    import jax

    return sum(int(getattr(x, "nbytes", 0)) for x in jax.live_arrays())


# ---------------------------------------------------------------------------
# Workload shapes (derived from the registry, the single source).


def shard_plan(ne_pad: int) -> int:
    """Smallest power-of-two shard count that brings the per-shard slab
    under SLAB_NE_MAX — how the billion-edge path actually arrives."""
    from cuvite_tpu.ops.segment import SLAB_NE_MAX

    s = 1
    while ne_pad // s > SLAB_NE_MAX:
        s *= 2
    return s


def audit_workloads() -> dict:
    """{name: {nv_pad, ne_pad, shards, ne_shard}} for the certification
    shapes: the largest REAL dataset class (Friendster) and the R-MAT
    scale-28 law — both derived from workloads/registry.py, never
    restated here."""
    from cuvite_tpu.core.types import next_pow2
    from cuvite_tpu.workloads import registry

    out = {}
    fr = registry.DATASETS["friendster"]
    pairs = [("friendster", fr.width_nv, fr.width_ne)]
    s_nv, s_ne = registry.rmat_scale_law(registry.RMAT_SCALE_MAX)
    pairs.append((f"rmat_s{registry.RMAT_SCALE_MAX}", s_nv, s_ne))
    for name, nv, ne in pairs:
        nv_pad, ne_pad = next_pow2(nv), next_pow2(ne)
        s = shard_plan(ne_pad)
        out[name] = {"nv_pad": nv_pad, "ne_pad": ne_pad, "shards": s,
                     "ne_shard": ne_pad // s}
    return out


# ---------------------------------------------------------------------------
# Jaxpr extraction: W001 walk + sort facts.


def _walk_eqns(jaxpr):
    from cuvite_tpu.analysis.jaxpr_audit import _sub_jaxprs

    stack = [jaxpr]
    while stack:
        jx = stack.pop()
        core = getattr(jx, "jaxpr", jx)
        for eqn in getattr(core, "eqns", ()):
            yield eqn
            for key in eqn.params:
                stack.extend(_sub_jaxprs(eqn.params[key]))


def index_width_findings(jaxpr, entry: str, index_bits: int) -> list:
    """W001: every index-carrying integer buffer in the trace must be
    wide enough for its operated extent."""
    out = []
    for eqn in _walk_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in _INDEX_PRIMS:
            continue
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is None or not getattr(aval, "shape", ()):
                continue
            dt = np.dtype(aval.dtype)
            if dt.kind not in "iu" or dt.itemsize * 8 > index_bits:
                continue
            cap = 2 ** (dt.itemsize * 8 - 1) - 1
            if name == "iota":
                dim = eqn.params.get("dimension", 0)
                extent = int(aval.shape[dim])
                worst = extent - 1  # iota's max emitted value
            else:
                ax = eqn.params.get("axis", 0)
                extent = int(aval.shape[ax])
                worst = extent    # a 0/1-mask cumsum can reach extent
            if worst > cap:
                out.append(_wfind(
                    "W001", entry,
                    f"'{entry}' traces an {dt.name} '{name}' over a "
                    f"{extent}-extent axis (max index {worst} > "
                    f"{cap}): the buffer is narrower than the "
                    f"manifest's index law ({index_bits} bits) allows "
                    "for this shape — a silent wraparound producing "
                    "wrong run ids/labels, not a crash",
                    snippet=name))
    return out


def sort_facts(jaxpr) -> list:
    """[(num_keys, key_dtype_name, key_ndim)] for every lax.sort
    equation in the trace — the observable that proves which comparator
    was selected.  ``key_ndim`` separates the 1-D edge-slab sort (the
    kbits+sbits pack under audit) from the bucketed row-argmax's 2-D
    per-row sort, whose int32 key is the community id itself."""
    facts = []
    for eqn in _walk_eqns(jaxpr):
        if eqn.primitive.name != "sort":
            continue
        nk = int(eqn.params.get("num_keys", 1))
        key = eqn.invars[0] if eqn.invars else None
        dt = np.dtype(key.aval.dtype).name if key is not None else "?"
        nd = len(getattr(key.aval, "shape", ())) if key is not None \
            else 0
        facts.append((nk, dt, nd))
    return facts


# ---------------------------------------------------------------------------
# Entries: each traces ONE real device-path program at (nv_pad,
# ne_shard) and returns its jaxpr.  All callables are the raw
# (unjitted) functions so nothing lands in the global jit caches.


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def _accum_for(ne: int):
    from cuvite_tpu.louvain.driver import _accum_name

    name = _accum_name(np.float32, float(ne), ne)
    return None if name == "float32" else name


def _trace_solo_sort(nv: int, ne: int):
    import jax
    import jax.numpy as jnp

    from cuvite_tpu.louvain.step import louvain_step_local

    def entry(src, dst, w, comm, vdeg, constant):
        out = louvain_step_local(src, dst, w, comm, vdeg, constant,
                                 nv_total=nv, axis_name=None,
                                 accum_dtype=_accum_for(ne))
        return out.target, out.modularity, out.n_moved

    return jax.make_jaxpr(entry)(
        _sds((ne,), jnp.int32), _sds((ne,), jnp.int32),
        _sds((ne,), jnp.float32), _sds((nv,), jnp.int32),
        _sds((nv,), jnp.float32), _sds((), jnp.float32))


def _trace_solo_fused(nv: int, ne: int):
    import jax
    import jax.numpy as jnp

    from cuvite_tpu.louvain.fused import fused_phase

    def entry(src, dst, w, constant):
        return fused_phase(src, dst, w, constant, 1e-6, nv_pad=nv,
                           accum_dtype=_accum_for(ne))

    return jax.make_jaxpr(entry)(
        _sds((ne,), jnp.int32), _sds((ne,), jnp.int32),
        _sds((ne,), jnp.float32), _sds((), jnp.float32))


def _trace_solo_bucketed(nv: int, ne: int):
    import jax
    import jax.numpy as jnp

    from cuvite_tpu.louvain.bucketed import bucketed_step

    # A synthetic-but-representative plan: three degree classes and a
    # heavy residual, rows covering the vertex space.  Only SHAPES
    # matter here; the plan-build host path has its own tier-1 tests.
    widths = (4, 16, 64)
    nb = max(nv // 8, 1)
    buckets = tuple(
        (_sds((nb,), jnp.int32), _sds((nb, d), jnp.int32),
         _sds((nb, d), jnp.float32))
        for d in widths)
    heavy = (_sds((ne // 4,), jnp.int32), _sds((ne // 4,), jnp.int32),
             _sds((ne // 4,), jnp.float32))

    def entry(bucket_arrays, heavy_arrays, self_loop, comm, vdeg,
              constant):
        return bucketed_step(bucket_arrays, heavy_arrays, self_loop,
                             comm, vdeg, constant, nv_total=nv,
                             sentinel=np.iinfo(np.int32).max,
                             accum_dtype=_accum_for(ne))

    return jax.make_jaxpr(entry)(
        buckets, heavy, _sds((nv,), jnp.float32), _sds((nv,), jnp.int32),
        _sds((nv,), jnp.float32), _sds((), jnp.float32))


def _trace_batched(nv: int, ne: int):
    import jax
    import jax.numpy as jnp

    from cuvite_tpu.louvain.fused import fused_phase
    from cuvite_tpu.workloads.registry import BATCH_MAX

    b, tnv, tne = BATCH_MAX, BATCHED_NV, BATCHED_NE

    def one(src, dst, w, constant):
        return fused_phase(src, dst, w, constant, 1e-6, nv_pad=tnv,
                           accum_dtype=None)

    def entry(src, dst, w, constant):
        return jax.vmap(one)(src, dst, w, constant)

    return jax.make_jaxpr(entry)(
        _sds((b, tne), jnp.int32), _sds((b, tne), jnp.int32),
        _sds((b, tne), jnp.float32), _sds((b,), jnp.float32))


def _trace_coarsen(nv: int, ne: int):
    import jax
    import jax.numpy as jnp

    from cuvite_tpu.coarsen.device import device_coarsen_slab

    def entry(src, dst, w, comm, real_mask):
        return device_coarsen_slab(src, dst, w, comm, real_mask,
                                   nv_pad=nv,
                                   accum_dtype=_accum_for(ne))

    return jax.make_jaxpr(entry)(
        _sds((ne,), jnp.int32), _sds((ne,), jnp.int32),
        _sds((ne,), jnp.float32), _sds((nv,), jnp.int32),
        _sds((nv,), jnp.bool_))


# name -> (tracer, sorts_expected): ``sorts_expected`` marks entries
# whose slab rides sort_edges_by_vertex_comm, where the ineligible-
# shape fallback check (no int32 single-key sort) applies.
ENTRIES = {
    "solo_sort_step": (_trace_solo_sort, True),
    "solo_fused_phase": (_trace_solo_fused, False),
    "solo_bucketed_step": (_trace_solo_bucketed, True),
    "batched_execute": (_trace_batched, False),
    "coarsen_coalesce": (_trace_coarsen, True),
}


def _pack_eligible(nv_pad: int, pack_bits: int) -> bool:
    """The segment.py packed-sort predicate at the step's bounds
    (src_bound = nv_local + 1, key_bound = nv_total)."""
    kbits = max(nv_pad - 1, 1).bit_length()
    sbits = max(nv_pad, 1).bit_length()
    return kbits + sbits <= pack_bits


# ---------------------------------------------------------------------------
# W002: boundary probes.


def boundary_probes(laws: dict) -> tuple:
    """(findings, facts) from probing every eligibility predicate at
    its widest-legal shape, one step past, and the forced-64 mode."""
    import jax
    import jax.numpy as jnp

    from cuvite_tpu.louvain.driver import DS_MIN_TOTAL_WEIGHT, _accum_name
    from cuvite_tpu.ops import segment

    findings: list = []
    facts: dict = {}
    pack_bits = int(laws.get("pack_bits", 31))
    ne = 1 << 10

    def sort_probe(kb, sb):
        def fn(src, ckey, w):
            return segment.sort_edges_by_vertex_comm(
                src, ckey, w, src_bound=1 << sb, key_bound=1 << kb)

        return sort_facts(jax.make_jaxpr(fn)(
            _sds((ne,), jnp.int32), _sds((ne,), jnp.int32),
            _sds((ne,), jnp.float32)))

    # Widest-legal: kbits+sbits == pack_bits -> ONE int32 key.
    legal = sort_probe(pack_bits - 15, 15)
    facts["sort_widest_legal"] = legal
    if (1, "int32", 1) not in legal:
        findings.append(_wfind(
            "W002", "packed_sort",
            f"at kbits+sbits == {pack_bits} (the widest legal packing) "
            f"the sort traced {legal}, not the single-key int32 packed "
            "comparator — the 4-5x fast path regressed at its own "
            "boundary"))
    # One past: the lexicographic two-key fallback, never int32 packed.
    past = sort_probe(pack_bits - 14, 15)
    facts["sort_one_past"] = past
    if any(nk == 1 and dt == "int32" for nk, dt, _nd in past):
        findings.append(_wfind(
            "W002", "packed_sort",
            f"at kbits+sbits == {pack_bits + 1} the sort still traced "
            f"an int32 single-key comparator ({past}): the packed key "
            "bleeds into the sign bit and rows sort to the FRONT — the "
            "eligibility predicate is not selecting the fallback"))
    elif not any(nk == 2 for nk, dt, _nd in past):
        findings.append(_wfind(
            "W002", "packed_sort",
            f"at kbits+sbits == {pack_bits + 1} no two-key "
            f"lexicographic sort appeared ({past}): the fallback "
            "comparator is missing"))
    # Forced-64: the same ineligible shape packs into ONE int64 key.
    x64_prior = jax.config.jax_enable_x64
    try:
        jax.config.update("jax_enable_x64", True)
        forced = sort_probe(pack_bits - 14, 15)
    finally:
        jax.config.update("jax_enable_x64", x64_prior)
    facts["sort_forced_64"] = forced
    if (1, "int64", 1) not in forced:
        findings.append(_wfind(
            "W002", "packed_sort",
            f"under jax_enable_x64 at kbits+sbits == {pack_bits + 1} "
            f"the sort traced {forced}, not the single-key int64 pack "
            "— the oracle mode lost the wide fast path"))

    # Raise-guard: legal shape traces; one past FAILS LOUD.
    slab_max = int(laws.get("slab_ne_max", segment.SLAB_NE_MAX))

    def runs(ne_probe, nv_probe=1 << 12):
        jax.eval_shape(
            lambda s, c, w: segment.coalesced_runs(
                s, c, w, nv_pad=nv_probe),
            _sds((ne_probe,), jnp.int32), _sds((ne_probe,), jnp.int32),
            _sds((ne_probe,), jnp.float32))

    try:
        runs(slab_max)
        facts["slab_at_max"] = "traced"
    except Exception as e:
        findings.append(_wfind(
            "W002", "slab_ne_max",
            f"coalesced_runs at ne_pad == SLAB_NE_MAX ({slab_max}) "
            f"failed to trace: {type(e).__name__}: {e} — the widest "
            "legal slab must stay admissible"))
    try:
        runs(slab_max * 2)
        findings.append(_wfind(
            "W002", "slab_ne_max",
            f"coalesced_runs accepted ne_pad == {slab_max * 2} (one "
            "doubling past SLAB_NE_MAX): the int32 run-id cumsums "
            "would wrap silently — the raise-guard is gone"))
    except ValueError:
        facts["slab_one_past"] = "raised"

    # ds32 cutover: exactly at DS_MIN_TOTAL_WEIGHT, via either gate
    # (weight mass or addend count).
    ds_min = float(laws.get("ds32_min", DS_MIN_TOTAL_WEIGHT))
    below = _accum_name(np.float32, ds_min - 1.0, 0)
    at = _accum_name(np.float32, ds_min, 0)
    by_n = _accum_name(np.float32, 0.0, int(ds_min))
    facts["accum"] = {"below": below, "at": at, "by_addends": by_n}
    if below != "float32" or at != "ds32" or by_n != "ds32":
        findings.append(_wfind(
            "W002", "ds32_cutover",
            f"_accum_name at the DS_MIN_TOTAL_WEIGHT boundary chose "
            f"(below={below!r}, at={at!r}, by_addends={by_n!r}); "
            "expected ('float32', 'ds32', 'ds32') — the threshold-"
            "safety cutover moved"))

    return findings, facts


# ---------------------------------------------------------------------------
# Manifest.


def load_budget(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        data = json.load(fh)
    if data.get("version") != BUDGET_VERSION:
        raise ValueError(f"width budget {path!r}: unsupported "
                         f"version {data.get('version')!r}")
    return data


def write_budget(path: str, doc: dict) -> None:
    out = dict(doc)
    out["version"] = BUDGET_VERSION
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(out, fh, indent=2, sort_keys=True)
        fh.write("\n")


def code_laws() -> dict:
    """The laws as the CODE declares them — what the manifest must
    match (W003 cross-check) and what --write-budget regenerates."""
    from cuvite_tpu.louvain.driver import DS_MIN_TOTAL_WEIGHT
    from cuvite_tpu.ops.segment import SLAB_NE_MAX

    return {
        "index_bits": 32,
        "pack_bits": 31,
        "slab_ne_max": SLAB_NE_MAX,
        "ds32_min": DS_MIN_TOTAL_WEIGHT,
    }


def manifest_crosscheck(manifest: dict) -> list:
    """W003: the checked-in manifest must agree with the code constants
    and the registry's declared max workload — a drifted manifest
    certifies shapes nobody ships."""
    from cuvite_tpu.workloads import registry

    out = []
    laws = manifest.get("laws", {})
    for key, want in sorted(code_laws().items()):
        got = laws.get(key)
        if got != want:
            out.append(_wfind(
                "W003", "manifest",
                f"tools/width_budget.json law '{key}' is {got!r} but "
                f"the code declares {want!r}: the manifest drifted — "
                "regenerate with tools/width_audit.py --write-budget "
                "and review the diff"))
    declared = manifest.get("max_workload", {})
    actual = registry.max_workload()
    if declared != actual:
        out.append(_wfind(
            "W003", "manifest",
            f"manifest max_workload {declared} != registry "
            f"max_workload() {actual}: the width envelope the static "
            "tier certifies against moved without the manifest"))
    if actual != MAX_WORKLOAD:
        out.append(_wfind(
            "W003", "manifest",
            f"registry.max_workload() {actual} != widthcheck."
            f"MAX_WORKLOAD {MAX_WORKLOAD}: the static and dynamic "
            "tiers certify DIFFERENT envelopes"))
    return out


# ---------------------------------------------------------------------------
# The audit.


def run_width_audit(entry_names=None, workloads=None,
                    budget_path: str | None = None,
                    probes: bool = True):
    """(findings, reports) over the certification workloads.

    ``reports``: {workload: {entry: {"sorts", "w001", "nv_pad",
    "ne_shard"}}} plus ``"probes"`` (boundary facts) and ``"spy"``
    (the live-buffer delta).  Results are NEVER cached."""
    import jax

    if budget_path is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        budget_path = os.path.join(root, DEFAULT_BUDGET_REL)
    findings: list = []
    reports: dict = {}
    try:
        manifest = load_budget(budget_path)
    except (OSError, ValueError) as e:
        manifest = None
        findings.append(_wfind(
            "W003", "manifest",
            f"width budget unreadable ({e}): the index-width law "
            "inventory is the closed artifact — restore "
            "tools/width_budget.json or regenerate with "
            "tools/width_audit.py --write-budget"))
    if manifest is not None:
        findings.extend(manifest_crosscheck(manifest))
    laws = (manifest or {}).get("laws") or code_laws()
    index_bits = int(laws.get("index_bits", 32))
    pack_bits = int(laws.get("pack_bits", 31))

    names = list(ENTRIES) if entry_names is None else list(entry_names)
    wl = audit_workloads()
    if workloads is not None:
        wl = {k: v for k, v in wl.items() if k in set(workloads)}

    # Warm up every selected entry at a tiny class first so lazily
    # created import-time buffers never pollute the spy's baseline.
    for name in names:
        tracer, _ = ENTRIES[name]
        try:
            tracer(1 << 8, 1 << 10)
        except Exception:
            pass  # the real run reports it as W003
    gc.collect()
    baseline = live_device_bytes()

    for wname, shapes in sorted(wl.items()):
        nv, ne = shapes["nv_pad"], shapes["ne_shard"]
        per: dict = {}
        for name in names:
            tracer, slab_sorts = ENTRIES[name]
            try:
                jaxpr = tracer(nv, ne)
            except Exception as e:  # fail CLOSED: a crashing entry is
                findings.append(_wfind(  # a finding, not a skipped check
                    "W003", name,
                    f"entry '{name}' failed to trace at workload "
                    f"'{wname}' (nv_pad={nv}, ne_shard={ne}): "
                    f"{type(e).__name__}: {e}"))
                continue
            w001 = index_width_findings(jaxpr, name, index_bits)
            findings.extend(w001)
            sorts = sort_facts(jaxpr)
            del jaxpr
            if slab_sorts and not _pack_eligible(nv, pack_bits) \
                    and any(nk == 1 and dt == "int32" and nd == 1
                            for nk, dt, nd in sorts):
                findings.append(_wfind(
                    "W002", name,
                    f"'{name}' at workload '{wname}' (nv_pad={nv}: "
                    f"kbits+sbits > {pack_bits}) still traced an int32 "
                    f"single-key sort ({sorts}): the lexicographic "
                    "fallback was NOT selected on the first ineligible "
                    "shape — packed keys are wrapping the sign bit"))
            per[name] = {"nv_pad": nv, "ne_shard": ne,
                         "sorts": sorts, "w001": len(w001)}
        reports[wname] = per

    if probes:
        probe_findings, probe_facts = boundary_probes(laws)
        findings.extend(probe_findings)
        reports["probes"] = probe_facts

    gc.collect()
    delta = live_device_bytes() - baseline
    reports["spy"] = {"baseline_bytes": baseline, "delta_bytes": delta}
    if delta != 0:
        findings.append(_wfind(
            "W003", "alloc_spy",
            f"the trace sweep allocated {delta} live device bytes; the "
            "scale-28 certification is only honest at ZERO — some "
            "entry concretized (device_put / block_until_ready / eager "
            "constant) instead of staging abstractly"))
    return findings, reports
