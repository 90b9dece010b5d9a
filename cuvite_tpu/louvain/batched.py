"""Batched multi-tenant Louvain: B same-class graphs, ONE compiled step
per phase (ISSUE 9).

Serving "millions of users" means thousands of small graphs (per-user
neighborhoods, per-session interaction graphs) arriving concurrently —
and for slab-class-canonicalized graphs the dominant cost of serving
them one at a time is per-job dispatch: the compiled-program launch,
the per-phase host sync, the Python driver overhead.  All of it is
amortizable, because every graph of one ``(nv_pad, ne_pad)`` class runs
the *same program on the same shapes*.  This driver stacks B such
slabs on a leading batch axis (core/batch.py) and runs the whole batch
through one jitted per-phase program:

  * ``jax.vmap`` of the fused phase loop (louvain/fused.py::fused_phase):
    under vmap the ``lax.while_loop`` iterates until EVERY row's phase
    converges, masking finished rows — so B phase loops cost
    max(iters_b) batched sweeps, not sum(iters_b) sequential ones;
  * the vmapped device coarsener (coarsen/device.py::batched_renumber /
    batched_compose_labels / batched_coarsen_slab): per-row dense
    renumbering, label composition and slab relabel+coalesce, all in
    HBM, landing every row's coarse graph back in the SAME class;
  * per-graph phase exit by MASKING, not batch splitting: a row whose
    phase fails the gain threshold keeps its composed labels and has
    its slab overwritten with padding — trailing phases cost it two
    masked sweeps, and the batch shape (the compile key) never changes.

One host sync per phase for the whole batch (driver._phase_sync — the
same chokepoint the per-graph drivers use, so the sync-spy tests cover
both), one compile per ``(class, B)``, and one final O(B * nv_pad)
label gather.  Labels and per-row Q are bit-identical to running the
same driver at B=1 — vmap lifts every op row-wise, and nothing in the
program mixes rows.

Batch-axis data parallelism.  The program is row-independent by
construction, so the batch axis shards over a 1-D device mesh with NO
collectives (``shard_map`` with every spec ``P('b')``): on a TPU slice
tenants spread across chips; on CPU the same split over
``--xla_force_host_platform_device_count`` virtual devices is what
makes batching pay — XLA:CPU executes a batched ``lax.sort`` serially
(measured: a [64, 16384] two-channel sort costs exactly 64x the
single-row sort on a 24-core host; sharded over 8 virtual devices it
drops 7.3x), so without the mesh a CPU batch amortizes dispatch but
serializes compute.  Each shard's ``while_loop`` trip count follows its
OWN rows (no collectives inside), so a shard whose tenants converge
early goes idle instead of pacing the batch.

Scope: fixed threshold, no cycling (the cycling safety-net pass
re-enters rows at different phases, which would fragment the batch; the
serving default is the reference's final threshold 1e-6 anyway), plain
schedule (no ET/coloring), single shard per row.  The per-graph drivers
in driver.py keep every other configuration.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from cuvite_tpu.coarsen.device import (
    batched_coarsen_slab,
    batched_compose_labels,
    batched_renumber,
    batched_subrow_compose,
    batched_subrow_renumber,
)
from cuvite_tpu.core.batch import (
    BATCH_ENGINES,
    BatchedSlab,
    PackedSubRows,
    batch_slabs,
)
from cuvite_tpu.core.types import (
    MAX_TOTAL_ITERATIONS,
    TERMINATION_PHASE_COUNT,
)
from cuvite_tpu.louvain.fused import fused_phase
from cuvite_tpu.obs.convergence import decode_phase_conv
from cuvite_tpu.ops import segment as seg
from cuvite_tpu.utils.upload import to_device

# Batched engines (canonical tuple: core.batch.BATCH_ENGINES, re-
# exported above): 'fused' — vmapped fused phase loop (the packed
# 2-channel lax.sort sweep) every phase; 'bucketed' — phase 0 runs the
# vmapped BUCKETED sweep over cross-graph-padded plans (ISSUE 10; the
# sort-free formulation every per-graph benchmark shows is the fast
# one), and phases >= 1 RE-BIN ON DEVICE (ISSUE 19): the coarse slab is
# re-bucketed inside the phase program by coarsen/rebin.py's histogram +
# gather builder, so coarse phases stay on the sort-free formulation
# too.  Classes the re-binner cannot certify (possible heavy residual,
# element budget — coarsen/rebin.py::rebin_eligible) and
# CUVITE_DEVICE_REBIN=0 fall back to the fused loop, the pre-ISSUE-19
# downgrade.  The per-phase engine actually used is recorded in
# BatchResult.phase_engines.


def _phase_body(src, dst, w, comm_all, real_mask, prev_mod, active,
                constant, threshold, *, nv_pad, accum_dtype,
                max_iters=MAX_TOTAL_ITERATIONS):
    """One Louvain phase for the whole batch: vmapped fused phase loop +
    gain test + vmapped device coarsening, converged rows masked.

    Row state (all leading-axis B): ``src/dst/w`` — the current coarse
    slab (dense ids, same class every phase); ``comm_all`` — original
    vertex -> current dense community id; ``real_mask`` — current real-
    vertex mask; ``prev_mod`` — last gaining phase's Q (or -1);
    ``active`` — row still clustering.  Returns the updated state plus
    per-row ``(gained, mod, iters, nc, ne2)`` scalars and the
    convergence telemetry buffers ``(cq, cmoved, covf)`` [B, CAP].

    Shape-polymorphic in the leading axis: jitted whole for the
    single-device program, or wrapped per-shard by
    :func:`_get_batched_phase` when the batch axis is sharded.
    """
    adt = accum_dtype

    past, mod, iters, _ovf, (cq, cmoved, covf) = jax.vmap(
        lambda s, d, ww, c: fused_phase(
            s, d, ww, c, threshold, nv_pad=nv_pad, accum_dtype=adt,
            max_iters=max_iters)
    )(src, dst, w, constant)

    return _phase_tail(
        src, dst, w, comm_all, real_mask, prev_mod, active, threshold,
        past, mod, iters, cq, cmoved, covf,
        nv_pad=nv_pad, accum_dtype=accum_dtype)


def _bucketed_phase_body(buckets, heavy, self_loop, perm, src, dst, w,
                         comm_all, real_mask, prev_mod, active, constant,
                         threshold, *, nv_pad, accum_dtype,
                         max_iters=MAX_TOTAL_ITERATIONS):
    """The sort-free phase: the per-graph BUCKETED sweep lifted over the
    batch axis (ISSUE 10).  Same contract as :func:`_phase_body`, plus
    the batched plan arrays (core/batch.py::batch_bucket_plans) ahead of
    the slab state.

    The row sweep is literally the per-graph bucketed driver's phase
    loop — ``driver._run_phase_loop`` over ``driver._bucketed_call``
    (identity start, on-device convergence check, the degree-bucketed
    dense row formulation of Naim et al., arXiv:1805.10904) — vmapped,
    so per-tenant labels stay bit-identical to a B=1 run.  Engine
    degradation under vmap: no Pallas row-argmax flags (the kernel grid
    does not lift over a batch axis; the XLA path it degrades to is
    bit-identical), and the heavy residual runs the sorted path on its
    (usually 8-slot padding) slab.  The slab itself is swept ONLY for
    the per-row weighted degrees — no per-iteration ne_pad-sized sort.

    The coarsen + masked-exit tail is shared with the fused body, so
    phase transitions cannot drift between engines.
    """
    from cuvite_tpu.louvain.driver import _bucketed_call, _run_phase_loop

    wdt = w.dtype
    sentinel = int(np.iinfo(np.int32).max)
    call = _bucketed_call(nv_pad, sentinel, accum_dtype)
    lower = jnp.asarray(-1.0, dtype=wdt)
    th = jnp.asarray(threshold, dtype=wdt)

    def one(bk, hv, sl, pm, s, ww, c):
        vdeg = seg.segment_sum(ww, s, num_segments=nv_pad,
                               sorted_ids=True)
        comm0 = jnp.arange(nv_pad, dtype=jnp.int32)
        extra = (bk, hv, sl, vdeg, c, pm)
        return _run_phase_loop(extra, comm0, th, lower, call=call,
                               max_iters=max_iters)

    past, mod, iters, _ovf, (cq, cmoved, covf) = jax.vmap(one)(
        buckets, heavy, self_loop, perm, src, w, constant)

    return _phase_tail(
        src, dst, w, comm_all, real_mask, prev_mod, active, threshold,
        past, mod, iters, cq, cmoved, covf,
        nv_pad=nv_pad, accum_dtype=accum_dtype)


def _rebinned_phase_body(src, dst, w, comm_all, real_mask, prev_mod,
                         active, constant, threshold, *, nv_pad,
                         accum_dtype, max_iters=MAX_TOTAL_ITERATIONS):
    """The sort-free COARSE phase (ISSUE 19): same 9-operand contract as
    :func:`_phase_body`, but the row sweep is the bucketed formulation
    over a plan built ON DEVICE from the coarse slab by
    :func:`cuvite_tpu.coarsen.rebin.rebin_plan` — degree histogram,
    static-ladder class assignment, gather into the stacked
    ``[rows, width]`` layout — vmapped over the batch.  The coarse slab
    rows satisfy the re-binner's contract by construction: the vmapped
    coalesce emits ascending compacted runs with a padding tail, the
    masked-exit rows are pure padding, and ``_shrink_batch`` preserves
    the prefix.  Plan geometry is derived from the static slab class
    (``src.shape[-1]``), so the program is one compile per (class, B)
    like the fused body it replaces; eligibility (no heavy residual
    possible, element budget) is the CALLER's gate —
    ``rebin_eligible`` must hold for this body's class.

    The coarsen + masked-exit tail is shared with the other bodies, so
    phase transitions cannot drift between engines.
    """
    from cuvite_tpu.coarsen.rebin import rebin_geometry, rebin_plan
    from cuvite_tpu.louvain.driver import _bucketed_call, _run_phase_loop

    wdt = w.dtype
    ne_pad = src.shape[-1]
    geom = rebin_geometry(nv_pad, ne_pad)
    sentinel = int(np.iinfo(np.int32).max)
    call = _bucketed_call(nv_pad, sentinel, accum_dtype)
    lower = jnp.asarray(-1.0, dtype=wdt)
    th = jnp.asarray(threshold, dtype=wdt)

    def one(s, d, ww, c):
        bk, hv, sl, pm = rebin_plan(s, d, ww, nv_pad=nv_pad, base=0,
                                    geometry=geom)
        vdeg = seg.segment_sum(ww, s, num_segments=nv_pad,
                               sorted_ids=True)
        comm0 = jnp.arange(nv_pad, dtype=jnp.int32)
        extra = (bk, hv, sl, vdeg, c, pm)
        return _run_phase_loop(extra, comm0, th, lower, call=call,
                               max_iters=max_iters)

    past, mod, iters, _ovf, (cq, cmoved, covf) = jax.vmap(one)(
        src, dst, w, constant)

    return _phase_tail(
        src, dst, w, comm_all, real_mask, prev_mod, active, threshold,
        past, mod, iters, cq, cmoved, covf,
        nv_pad=nv_pad, accum_dtype=accum_dtype)


def _subrow_phase_body(src, dst, w, comm_all, real_mask, prev_mod, active,
                       constants, threshold, *, nv_pad, n_sub, accum_dtype,
                       max_iters=MAX_TOTAL_ITERATIONS):
    """The PACKED phase (ISSUE 20): ``n_sub`` fenced small graphs per
    row, the whole batch through the vmapped sub-row sweep
    (louvain/subrow.py).  Same 9-operand contract as :func:`_phase_body`
    except everything per-GRAPH is ``[B, n_sub]`` instead of ``[B]``:
    ``prev_mod``/``active``/``constants`` in, and the tail's
    ``(gained, mod, iters, nc, ne2)`` out (telemetry ``cq``/``cmoved``
    are ``[B, n_sub, CAP]``).  ``n_sub`` is the STATIC layout class —
    which tenants occupy which sub-row is batch content and never
    reaches a static (the B002 audit pins this for a packed batch).

    ``comm_all`` keeps the ORIGINAL row width even after the slab
    class shrinks — its trailing dim fixes the pack-time ``nv_sub0``
    for the two-offset-space coarsening (coarsen/device.py)."""
    from cuvite_tpu.louvain.subrow import subrow_phase

    past, mod, iters, _ovf, (cq, cmoved, covf) = jax.vmap(
        lambda s, d, ww, c: subrow_phase(
            s, d, ww, c, threshold, nv_pad=nv_pad, n_sub=n_sub,
            accum_dtype=accum_dtype, max_iters=max_iters)
    )(src, dst, w, constants)

    return _subrow_phase_tail(
        src, dst, w, comm_all, real_mask, prev_mod, active, threshold,
        past, mod, iters, cq, cmoved, covf,
        nv_pad=nv_pad, n_sub=n_sub)


def _subrow_phase_tail(src, dst, w, comm_all, real_mask, prev_mod, active,
                       threshold, past, mod, iters, cq, cmoved, covf, *,
                       nv_pad, n_sub):
    """Phase epilogue of the packed engine: the gain test, coarsening
    and masked exit of :func:`_phase_tail`, all at SUB-row granularity.
    Retired sub-rows' edges are masked to the row sentinel BEFORE the
    whole-row coalesce (so they compact away and batch-mates inherit a
    pure padding tail), and ``comm_all`` is composed through the
    ORIGINAL-offset dense map so final labels always live in pack-time
    offsets — unpack stays a fence slice regardless of when each
    sub-row retired or whether the slab class shrank."""
    wdt = w.dtype
    nv_sub = nv_pad // n_sub
    nv_sub0 = comm_all.shape[-1] // n_sub
    mod = mod.astype(wdt)
    gained = active & ((mod - prev_mod) > threshold)      # [B, n_sub]

    dmap_cur, dmap_orig, nc = batched_subrow_renumber(
        past, real_mask, nv_pad=nv_pad, n_sub=n_sub, nv_sub0=nv_sub0)
    comm_all2 = batched_subrow_compose(
        dmap_orig, past, comm_all, nv_pad=nv_pad, n_sub=n_sub,
        nv_sub0=nv_sub0)

    # Pre-coalesce retire: non-gaining sub-rows' edges -> row sentinel.
    seg_e = jnp.minimum(jnp.minimum(src, nv_pad - 1) // nv_sub, n_sub - 1)
    keep = (src < nv_pad) & jnp.take_along_axis(gained, seg_e, axis=1)
    src_m = jnp.where(keep, src, jnp.asarray(nv_pad, src.dtype))
    dst_m = jnp.where(keep, dst, jnp.zeros_like(dst))
    w_m = jnp.where(keep, w, jnp.zeros_like(w))

    # Relabel through the CURRENT-offset segment-local map + whole-row
    # coalesce — the device_coarsen_slab body with subrow maps (fences
    # keep every run single-sub-row, so run sums are bit-identical to
    # the solo slab's).  Packed rows are f32-only: accum stays None.
    def one(s, d, ww, c, dm):
        pad = s >= nv_pad
        cs = jnp.take(dm, jnp.take(c, jnp.minimum(s, nv_pad - 1)))
        cd = jnp.take(dm, jnp.take(c, d))
        ns = jnp.where(pad, jnp.asarray(nv_pad, s.dtype), cs.astype(s.dtype))
        nd = jnp.where(pad, jnp.zeros((), d.dtype), cd.astype(d.dtype))
        wi = jnp.where(pad, jnp.zeros_like(ww), ww)
        s2, d2, w2, _ = seg.coalesced_runs(
            ns, nd, wi, nv_pad=nv_pad, accum_dtype=None)
        return s2, d2, w2.astype(wdt)

    src2, dst2, w2 = jax.vmap(one)(src_m, dst_m, w_m, past, dmap_cur)

    # Per-sub-row coarse edge count (the shrink decision's ne2).
    seg2 = jnp.minimum(jnp.minimum(src2, nv_pad - 1) // nv_sub, n_sub - 1)
    ne2 = jax.vmap(
        lambda sid, rr: seg.segment_sum(rr, sid, num_segments=n_sub)
    )(seg2, (src2 < nv_pad).astype(jnp.int32))

    # Masked per-SUB-row exit: gaining sub-rows advance to per-segment
    # real-mask prefixes; retired ones go dark (labels already frozen
    # in comm_all at original offsets).
    segv = jnp.arange(nv_pad, dtype=jnp.int32) // nv_sub
    rloc = jnp.arange(nv_pad, dtype=jnp.int32) % nv_sub
    rm_o = (rloc[None, :] < jnp.take(nc, segv, axis=1)) \
        & jnp.take(gained, segv, axis=1)
    segp = jnp.arange(comm_all.shape[-1], dtype=jnp.int32) // nv_sub0
    gp = jnp.take(gained, segp, axis=1)
    comm_all_o = jnp.where(gp, comm_all2, comm_all)
    lower = jnp.asarray(-1.0, dtype=wdt)
    prev_o = jnp.where(gained, jnp.maximum(mod, lower), prev_mod)

    return (src2, dst2, w2, comm_all_o, rm_o, prev_o,
            gained, mod, iters, nc, ne2, cq, cmoved, covf)


def _phase_tail(src, dst, w, comm_all, real_mask, prev_mod, active,
                threshold, past, mod, iters, cq, cmoved, covf, *,
                nv_pad, accum_dtype):
    """Shared phase epilogue (every batched engine): gain test, vmapped
    device coarsening, masked per-row phase exit.  One definition so the
    fused and bucketed phases retire rows and advance slabs
    identically."""
    wdt = w.dtype
    mod = mod.astype(wdt)
    gained = active & ((mod - prev_mod) > threshold)

    # Vmapped device coarsener: dense renumber (reused by the label
    # composition), relabel+coalesce back into the same slab class.
    # Run sums accumulate in ds32 pairs exactly when the in-loop Q does
    # (the same scale gate the per-graph drivers apply).
    acc = "ds32" if accum_dtype == "ds32" else None
    dmap, nc = batched_renumber(past, real_mask, nv_pad=nv_pad)
    comm_all2 = batched_compose_labels(dmap, past, comm_all)
    src2, dst2, w2, _dm, _nc, ne2 = batched_coarsen_slab(
        src, dst, w, past, real_mask, dmap, nc,
        nv_pad=nv_pad, accum_dtype=acc)
    rm2 = jnp.arange(nv_pad, dtype=jnp.int32)[None, :] < nc[:, None]

    # Masked phase exit: a gaining row advances to its coarse slab; a
    # non-gaining (or already-inactive) row keeps its labels and has its
    # slab retired to pure padding — trailing phases then cost it two
    # masked sweeps, and the batch never splits or changes shape.
    g2 = gained[:, None]
    src_o = jnp.where(g2, src2, jnp.full_like(src, nv_pad))
    dst_o = jnp.where(g2, dst2, jnp.zeros_like(dst))
    w_o = jnp.where(g2, w2, jnp.zeros_like(w))
    rm_o = jnp.where(g2, rm2, jnp.zeros_like(real_mask))
    comm_all_o = jnp.where(g2, comm_all2, comm_all)
    lower = jnp.asarray(-1.0, dtype=wdt)
    prev_o = jnp.where(gained, jnp.maximum(mod, lower), prev_mod)

    return (src_o, dst_o, w_o, comm_all_o, rm_o, prev_o,
            gained, mod, iters, nc, ne2, cq, cmoved, covf)


# The batch-axis mesh dimension name (tenant-parallel; orthogonal to the
# vertex-sharding axis the SPMD engines use for ONE big graph).
BATCH_AXIS = "b"

# Serving-coarse slab-class floors (engine='bucketed', ISSUE 10).  The
# per-graph drivers shrink every coarse slab to its pow2 class
# (coarsen/device.py::maybe_shrink_to_class); PR 9's batched driver kept
# the PHASE-0 class for every phase, so coarse phases swept mostly
# padding — at the serving class (4096, 16384) a 7-community coarse
# graph still paid a [16384] 2-channel sort per iteration.  The
# bucketed engine lifts the shrink to the batch: ONE notch, decided
# after phase 0 from the (nc, ne2) scalars the per-phase sync already
# carries — the whole batch drops to `_coarse_class` iff every active
# row fits, else it stays put.  Binary decision -> at most two compiled
# fused-phase programs per (class, B), and B=1 decides identically, so
# served == solo bit-identity is preserved by construction.
BATCH_COARSE_MIN_NV = 1024
BATCH_COARSE_MIN_NE = 4096


def _coarse_class(nv_pad: int, ne_pad: int) -> tuple:
    """The one-notch serving-coarse class of a phase-0 slab class:
    divide by 4 (one pow2 class per dimension is too timid — measured:
    phase-0 coarsening collapses synth/R-MAT tenants far below it),
    floored at the serving-coarse minima."""
    return (max(nv_pad // 4, BATCH_COARSE_MIN_NV),
            max(ne_pad // 4, BATCH_COARSE_MIN_NE))


@functools.partial(jax.jit, static_argnames=("cnv", "cne"))
def _shrink_batch(src, dst, w, real_mask, *, cnv: int, cne: int):
    """Device-side batched slab-class shrink: per-row prefix slice +
    padding-sentinel rewrite (coarse ids are dense and < nc <= cnv, so
    only old sentinels move — the vmapped analog of
    coarsen/device.py::shrink_slab) plus the real-mask prefix."""
    s = src[:, :cne]
    s = jnp.where(s >= cnv, jnp.asarray(cnv, s.dtype), s)
    return s, dst[:, :cne], w[:, :cne], real_mask[:, :cnv]


@functools.partial(jax.jit,
                   static_argnames=("n_sub", "nv_sub", "cnv_sub", "cne_sub"))
def _shrink_subrow_batch(src, dst, w, real_mask, *, n_sub: int,
                         nv_sub: int, cnv_sub: int, cne_sub: int):
    """Sub-row analog of :func:`_shrink_batch`: every FENCE interval
    shrinks from ``nv_sub`` to ``cnv_sub`` vertices, so dense coarse ids
    remap ``s*nv_sub + r -> s*cnv_sub + r`` (each sub-row's ids are
    dense < its nc <= cnv_sub, so the remap is exact) and the real mask
    keeps each segment's prefix.  Edges slice to the row prefix — the
    coalesce compacts real runs there and the caller's per-sub-row ne2
    gate bounds their total by ``n_sub * cne_sub``.  ``comm_all`` is
    NOT remapped: it lives in pack-time offsets by construction."""
    nv_pad = n_sub * nv_sub
    cnv = n_sub * cnv_sub
    cne = n_sub * cne_sub

    def remap(x):
        return ((x // nv_sub) * cnv_sub
                + jnp.minimum(x % nv_sub, cnv_sub - 1)).astype(x.dtype)

    s = src[:, :cne]
    s = jnp.where(s >= nv_pad, jnp.asarray(cnv, s.dtype),
                  remap(jnp.minimum(s, nv_pad - 1)))
    d = remap(dst[:, :cne])
    B = real_mask.shape[0]
    rm = real_mask.reshape(B, n_sub, nv_sub)[:, :, :cnv_sub]
    return s, d, w[:, :cne], rm.reshape(B, cnv)


# Compiled batched-phase programs, keyed by (mesh devices, statics) —
# the "one compile per (class, B)" cache.  jax.jit already caches per
# callable+shapes; this table keeps the CALLABLE identity stable across
# batches so that cache engages (same pattern as driver._STEP_CACHE).
_PHASE_CACHE: dict = {}


def _get_batched_phase(mesh, nv_pad, accum_dtype, max_iters,
                       engine: str = "fused", n_buckets: int = 0,
                       n_sub: int = 0):
    """The compiled batched-phase program for one ``(mesh, class
    statics, engine)`` — ``engine='bucketed'`` adds the plan pytree
    (``n_buckets`` triples + heavy/self_loop/perm) ahead of the slab
    state; ``engine='rebinned'`` keeps the fused 9-operand signature
    (its plan is built inside the program); ``engine='subrow'`` also
    keeps it, with the per-graph operands widened to ``[B, n_sub]``
    (ISSUE 20 — ``n_sub`` is the static LAYOUT class; sub-row occupancy
    stays batch content).  jax.jit still caches per shapes, so a
    bucketed program is one compile per (class, B, bucket geometry)."""
    key = (
        None if mesh is None else tuple(d.id for d in mesh.devices.flat),
        nv_pad, accum_dtype, max_iters, engine, n_buckets,
        n_sub,
    )
    fn = _PHASE_CACHE.get(key)
    if fn is not None:
        return fn
    bucketed = engine == "bucketed"
    if engine == "subrow":
        body = functools.partial(
            _subrow_phase_body, nv_pad=nv_pad, n_sub=n_sub,
            accum_dtype=accum_dtype, max_iters=max_iters)
    else:
        body = functools.partial(
            {"bucketed": _bucketed_phase_body,
             "rebinned": _rebinned_phase_body,
             "fused": _phase_body}[engine],
            nv_pad=nv_pad, accum_dtype=accum_dtype, max_iters=max_iters)
    if mesh is None:
        fn = jax.jit(body)
    else:
        from jax.sharding import PartitionSpec as P

        b = P(BATCH_AXIS)
        # Row-independent SPMD: every batched operand/output splits on
        # the batch axis, the threshold scalar replicates, and the body
        # contains NO collectives — each shard's while_loop paces only
        # its own rows (check_vma off: nothing is replicated to check).
        if bucketed:
            bspec = tuple((b, b, b) for _ in range(n_buckets))
            in_specs = (bspec, (b, b, b)) + (b,) * 10 + (P(),)
        else:
            in_specs = (b,) * 8 + (P(),)
        fn = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=in_specs,
            out_specs=(b,) * 14,
            check_vma=False,
        ))
    _PHASE_CACHE[key] = fn
    return fn


def make_batch_mesh(b_pad: int, devices=None):
    """A 1-D batch-axis mesh over the largest pow2 device count that
    DIVIDES ``b_pad`` (shard_map needs the batch axis divisible by the
    mesh; ladder-rung b_pads are pow2 so every pow2 <= them divides,
    but an explicit caller b_pad may not be).  Returns None when one
    device (or one row) makes sharding pointless — the caller then
    runs the plain jitted program.
    """
    import numpy as _np

    devs = list(jax.devices()) if devices is None else list(devices)
    if b_pad <= 1 or len(devs) <= 1:
        return None
    from jax.sharding import Mesh

    cap = 1 << (len(devs).bit_length() - 1)     # largest pow2 <= ndev
    nd = min(b_pad & -b_pad, cap)               # largest pow2 | b_pad
    if nd <= 1:
        return None
    return Mesh(_np.array(devs[:nd]), (BATCH_AXIS,))


@dataclasses.dataclass
class BatchResult:
    """Per-tenant results plus the batch-level serving telemetry."""

    results: list          # list[LouvainResult], one per REAL job, in order
    wall_s: float          # whole-batch wall time (upload -> final gather)
    n_phases: int          # batch phase count (max over rows)
    b_pad: int
    n_jobs: int
    slab_class: tuple      # (nv_pad, ne_pad)
    # Engine telemetry (ISSUE 10/19): the engine each batch phase
    # actually ran — ['bucketed', 'rebinned', ...] under
    # engine='bucketed' (phase 0 sort-free over pack-time plans, coarse
    # phases over device-rebuilt plans; 'fused' where the re-binner
    # cannot certify the class or CUVITE_DEVICE_REBIN=0), all-'fused'
    # otherwise.
    phase_engines: list = dataclasses.field(default_factory=list)
    # The serving-coarse class phases >= 1 ran at (engine='bucketed'
    # whose post-phase-0 batch fit `_coarse_class`), else None.
    coarse_class: tuple | None = None
    # Pipeline-stage split of wall_s (ISSUE 14): host pack + upload vs
    # compiled-program execution — the two stages the pipelined
    # dispatcher overlaps (steady-state batch period = max, not sum).
    pack_s: float = 0.0
    device_s: float = 0.0
    # Sub-rows per batch row (ISSUE 20): 1 for plain batches, the
    # layout's n_sub for a packed batch (phase_engines then reads
    # ['subrow', ...]).
    n_sub: int = 1

    @property
    def pack_util(self) -> float:
        """Row occupancy — saturates at 1.0 the moment every row holds
        one tenant; see ``subrow_util`` for merged-batch honesty."""
        return min(self.n_jobs, self.b_pad) / max(self.b_pad, 1)

    @property
    def subrow_util(self) -> float:
        """Real graphs over total SUB-row capacity (== pack_util for
        plain batches, where n_sub == 1)."""
        return self.n_jobs / max(self.b_pad * self.n_sub, 1)

    @property
    def jobs_per_s(self) -> float:
        return self.n_jobs / max(self.wall_s, 1e-9)


def accum_class_of(graph, nv_pad: int | None = None) -> str:
    """The in-loop accumulator tag this graph runs solo THROUGH THE
    BATCHED DRIVER (``louvain_many([g])``; 'float32', or 'ds32' past
    the DS_MIN_TOTAL_WEIGHT scale gate) — the second half of the
    serving bin key.  Rows of one batch must share it: the accumulator
    is a per-PROGRAM static, so a batch mixing a ds32-scale tenant with
    f32 ones would run every row ds32 and silently break the
    served-equals-solo bit-identity contract for the small rows.

    The addend count floors at ``nv_pad`` (the padded reduction length
    the batched program actually sums over) where the per-graph fused
    driver floors at the REAL vertex count — deliberately one notch
    more conservative: a graph whose padding alone crosses the gate
    runs ds32 here, consistently at every B, while its
    ``louvain_phases`` run may stay f32."""
    from cuvite_tpu.core.batch import slab_class_of
    from cuvite_tpu.louvain.driver import _accum_name

    if nv_pad is None:
        nv_pad = slab_class_of(graph)[0]
    return _accum_name(np.float32, graph.total_edge_weight_twice(),
                       max(graph.num_edges, nv_pad))


def _batch_accum_name(batch: BatchedSlab) -> str:
    """Static accumulator tag for the whole batch — rows must agree
    (see :func:`accum_class_of`; the serving queue bins by it, so a
    mixed batch here is a caller bug, not a degradable state)."""
    from cuvite_tpu.louvain.driver import _accum_name

    names = {
        _accum_name(np.float32, float(batch.tw2[i]),
                    max(int(batch.ne_real[i]), batch.nv_pad))
        for i in range(batch.b_pad) if batch.row_valid[i]
    }
    if len(names) > 1:
        raise ValueError(
            f"mixed accumulator classes {sorted(names)} in one batch: "
            "a per-program static accumulator would silently change "
            "the f32 rows' results vs their solo runs — bin jobs by "
            "(slab_class_of, accum_class_of) before packing "
            "(serve/queue.py does)")
    return names.pop() if names else "float32"


@dataclasses.dataclass
class PreparedBatch:
    """A packed batch with its device buffers ALREADY uploaded — the
    handoff unit of the pipelined dispatcher (ISSUE 14): the packer
    stage builds one of these (host pack + plan build + upload) while
    the executor stage runs the previous batch's compiled program
    (:func:`execute_prepared`).  The initial device refs are never
    mutated by execution, so a transient device fault can re-run
    ``execute_prepared`` on the same PreparedBatch and get bit-identical
    results without re-packing."""

    # Host metadata (what the phase loop needs from the BatchedSlab).
    b_pad: int
    nv_pad: int
    ne_pad: int
    n_jobs: int
    slab_class: tuple
    nv_real: np.ndarray
    ne_real: np.ndarray
    row_valid: np.ndarray
    # Statics of the compiled program set.
    adt: str
    mesh: object
    engine: str
    n_buckets: int
    # Device refs (phase-0 state; plans None for engine='fused').
    src_d: object = None
    dst_d: object = None
    w_d: object = None
    rm_d: object = None
    const_d: object = None
    comm_all_d: object = None
    prev_d: object = None
    plan_d: object = None
    # Host pack + upload wall seconds (the packer-stage cost).
    pack_s: float = 0.0
    # Sub-row layout (engine='subrow', ISSUE 20): n_sub > 1 widens the
    # per-graph metadata — nv_real/ne_real/sub_valid and the prev/const
    # device refs are [B, n_sub]; row_valid stays the [B] row-level OR.
    n_sub: int = 1
    sub_valid: np.ndarray | None = None


def prepare_batch(batch: BatchedSlab, *, mesh="auto", engine: str = "fused",
                  bucket_shape=None, tracer=None) -> PreparedBatch:
    """The PACK half of :func:`run_batched`: validate the batch's
    statics, build the bucket plans (engine='bucketed'), resolve the
    batch mesh, and upload every device buffer (``plan``/``upload``
    stages, HBM-ledger tracked).  Contains no compiled-program
    execution — in the pipelined dispatcher this runs on the packer
    thread while the executor thread runs the previous batch."""
    from cuvite_tpu.core.batch import batch_bucket_plans

    if engine not in BATCH_ENGINES:
        raise ValueError(f"unknown batched engine {engine!r}; "
                         f"use one of {BATCH_ENGINES}")
    if tracer is None:
        from cuvite_tpu.utils.trace import NullTracer

        tracer = NullTracer()

    t0 = time.perf_counter()
    B = batch.b_pad
    nv_pad = batch.nv_pad
    wdt = np.dtype(np.float32)
    adt = _batch_accum_name(batch)
    if mesh == "auto":
        mesh = make_batch_mesh(B)
    bplan = None
    n_buckets = 0
    if engine == "bucketed":
        # Plans are built AT PACK TIME, before any device work — the
        # plan-per-job trap (building them inside a dispatch loop) is
        # what graftlint R015 guards against in serve/.
        with tracer.stage("plan"):
            bplan = batch_bucket_plans(batch, shape=bucket_shape)
        n_buckets = len(bplan.buckets)

    def _place(x):
        if mesh is None:
            return to_device(x)
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(x, NamedSharding(mesh, P(BATCH_AXIS)))

    with tracer.stage("upload"):
        src_d = _place(batch.src)
        dst_d = _place(batch.dst)
        w_d = _place(batch.w)
        rm_d = _place(batch.real_mask)
        const_d = _place(batch.constant)
        comm_all_d = _place(np.broadcast_to(
            np.arange(nv_pad, dtype=np.int32)[None, :],
            (B, nv_pad)).copy())
        prev_d = _place(np.full((B,), -1.0, dtype=wdt))
        plan_d = None
        if bplan is not None:
            # verts cast to the device vertex dtype; weights stay f32
            # (the plan builder's stable-compile-key contract — see
            # core/batch.py); every array shards on the batch axis like
            # the slab.  The execute loop drops ITS plan reference after
            # phase 0; the PreparedBatch keeps this one so a transient
            # device fault can re-run execution without re-uploading.
            plan_d = (
                tuple((_place(v.astype(np.int32)), _place(d), _place(ww))
                      for v, d, ww in bplan.buckets),
                tuple(_place(a) for a in bplan.heavy),
                _place(bplan.self_loop),
                _place(bplan.perm),
            )
            bplan = None  # the host-side plan copy is dead weight too

    return PreparedBatch(
        b_pad=B, nv_pad=nv_pad, ne_pad=batch.ne_pad, n_jobs=batch.n_jobs,
        slab_class=batch.slab_class, nv_real=batch.nv_real.copy(),
        ne_real=batch.ne_real.copy(),
        row_valid=np.asarray(batch.row_valid).copy(),
        adt=adt, mesh=mesh, engine=engine,
        n_buckets=n_buckets,
        src_d=src_d, dst_d=dst_d, w_d=w_d, rm_d=rm_d, const_d=const_d,
        comm_all_d=comm_all_d, prev_d=prev_d, plan_d=plan_d,
        pack_s=time.perf_counter() - t0,
    )


def prepare_packed(packed: PackedSubRows, *, mesh="auto",
                   tracer=None) -> PreparedBatch:
    """The PACK half of a sub-row merged batch (ISSUE 20): accumulator
    gate + mesh resolve + device upload, the packed analog of
    :func:`prepare_batch` (``engine='subrow'``, no plans).  The gate
    re-evaluates every tenant's accumulator class AT THE ROW CLASS —
    ``accum_class_of(g, nv_pad=row_nv_pad)`` — because the packed
    program's reductions run over the row's padded length: a tenant f32
    at its own class can cross the ds32 scale gate at the row class, and
    a per-program accumulator flip would change its batch-mates' bits.
    The serving merge packer applies the same gate before merging; this
    raise is the backstop for direct callers."""
    from cuvite_tpu.louvain.driver import _accum_name

    if tracer is None:
        from cuvite_tpu.utils.trace import NullTracer

        tracer = NullTracer()

    t0 = time.perf_counter()
    B = packed.b_pad
    nv_pad = packed.nv_pad
    n_sub = packed.layout.n_sub
    wdt = np.dtype(np.float32)
    bad = sorted({
        _accum_name(np.float32, float(packed.tw2[i, s]),
                    max(int(packed.ne_real[i, s]), nv_pad))
        for i in range(B) for s in range(n_sub) if packed.sub_valid[i, s]
    } - {"float32"})
    if bad:
        raise ValueError(
            f"prepare_packed: accumulator classes {bad} at the row "
            f"class nv_pad={nv_pad} — packed rows are f32-only; gate "
            "tenants with accum_class_of(g, nv_pad=row_nv_pad) before "
            "merging (serve/queue.py does)")
    adt = "float32"
    if mesh == "auto":
        mesh = make_batch_mesh(B)

    def _place(x):
        if mesh is None:
            return to_device(x)
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(x, NamedSharding(mesh, P(BATCH_AXIS)))

    with tracer.stage("upload"):
        src_d = _place(packed.src)
        dst_d = _place(packed.dst)
        w_d = _place(packed.w)
        rm_d = _place(packed.real_mask)
        const_d = _place(packed.constants)
        comm_all_d = _place(np.broadcast_to(
            np.arange(nv_pad, dtype=np.int32)[None, :],
            (B, nv_pad)).copy())
        prev_d = _place(np.full((B, n_sub), -1.0, dtype=wdt))

    return PreparedBatch(
        b_pad=B, nv_pad=nv_pad, ne_pad=packed.ne_pad,
        n_jobs=packed.n_jobs, slab_class=packed.slab_class,
        nv_real=packed.nv_real.copy(), ne_real=packed.ne_real.copy(),
        row_valid=np.asarray(packed.row_valid).copy(),
        adt=adt, mesh=mesh, engine="subrow", n_buckets=0,
        src_d=src_d, dst_d=dst_d, w_d=w_d, rm_d=rm_d, const_d=const_d,
        comm_all_d=comm_all_d, prev_d=prev_d,
        pack_s=time.perf_counter() - t0,
        n_sub=n_sub, sub_valid=packed.sub_valid.copy(),
    )


def execute_prepared(prep: PreparedBatch, *, threshold: float = 1.0e-6,
                     max_phases: int = TERMINATION_PHASE_COUNT,
                     tracer=None, verbose: bool = False) -> BatchResult:
    """The EXECUTE half of :func:`run_batched`: run the compiled
    per-phase programs over an uploaded batch (one host sync per phase,
    one final label gather).  Re-runnable: the PreparedBatch's device
    refs are read-only here, so a retry restarts from phase 0 with
    bit-identical results."""
    from cuvite_tpu.louvain.driver import (
        LouvainResult,
        PhaseStats,
        _phase_sync,
    )

    if prep.engine == "subrow":
        return _execute_subrow(prep, threshold=threshold,
                               max_phases=max_phases, tracer=tracer,
                               verbose=verbose)

    if tracer is None:
        from cuvite_tpu.utils.trace import NullTracer

        tracer = NullTracer()

    t0 = time.perf_counter()
    B = prep.b_pad
    nv_pad = prep.nv_pad
    cur_nv, cur_ne = nv_pad, prep.ne_pad  # slab class of the NEXT phase
    coarse_class = None
    wdt = np.dtype(np.float32)
    adt = prep.adt
    mesh = prep.mesh

    def _coarse_fn(nv, ne):
        # Coarse-phase program of the current slab class: under
        # engine='bucketed', device re-binning (ISSUE 19) keeps coarse
        # phases on the sort-free bucketed formulation whenever the
        # re-binner can certify the class (no heavy residual possible,
        # element budget) and CUVITE_DEVICE_REBIN is on; otherwise the
        # pre-ISSUE-19 fused downgrade.
        from cuvite_tpu.coarsen.rebin import (
            device_rebin_enabled,
            rebin_eligible,
        )

        if (prep.engine == "bucketed" and device_rebin_enabled()
                and rebin_eligible(nv, ne)):
            return _get_batched_phase(
                mesh, nv, adt, MAX_TOTAL_ITERATIONS,
                engine="rebinned"), "rebinned"
        return _get_batched_phase(mesh, nv, adt,
                                  MAX_TOTAL_ITERATIONS), "fused"

    phase_fn, coarse_engine = _coarse_fn(nv_pad, prep.ne_pad)
    phase0_fn = None
    if prep.engine == "bucketed":
        phase0_fn = _get_batched_phase(
            mesh, nv_pad, adt, MAX_TOTAL_ITERATIONS,
            engine="bucketed", n_buckets=prep.n_buckets)
    src_d, dst_d, w_d = prep.src_d, prep.dst_d, prep.w_d
    rm_d, const_d = prep.rm_d, prep.const_d
    comm_all_d, prev_d, plan_d = prep.comm_all_d, prep.prev_d, prep.plan_d

    active = prep.row_valid.copy()

    # Host-side per-row bookkeeping.
    nv_cur = prep.nv_real.copy()
    ne_cur = prep.ne_real.copy()
    tot_iters = np.zeros(B, dtype=np.int64)
    row_phases: list = [[] for _ in range(B)]
    row_conv: list = [[] for _ in range(B)]
    phase_engines: list = []
    phase = 0

    while active.any() and phase < max_phases:
        t1 = time.perf_counter()
        active_at_start = active.copy()
        # Phase 0 under engine='bucketed' runs the sort-free vmapped
        # bucketed sweep over the pack-time plans; coarse phases re-bin
        # their plans on device when eligible ('rebinned', ISSUE 19),
        # else run the fused loop (also every phase of engine='fused').
        # The engine per phase is recorded for telemetry/bench
        # provenance.
        bucketed_phase = phase == 0 and phase0_fn is not None
        phase_engines.append("bucketed" if bucketed_phase
                             else coarse_engine)
        # HBM ledger: re-track the live set per phase, so the phase-0
        # plan buffers leave the accounting once dropped and the slab
        # bytes follow the serving-coarse shrink (the snapshot below
        # must report what is actually resident, not the upload-time
        # high-water).
        tracer.ledger_phase_begin()
        tracer.track("slab", src_d, dst_d, w_d)
        tracer.track("tables", rm_d, const_d)
        if plan_d is not None:
            tracer.track("plans", *jax.tree_util.tree_leaves(plan_d))
        with tracer.stage("iterate"):
            if bucketed_phase:
                (src_d, dst_d, w_d, comm_all_d, rm_d, prev_d,
                 gained_d, mod_d, iters_d, nc_d, ne2_d,
                 cq_d, cmoved_d, covf_d) = phase0_fn(
                    *plan_d,
                    src_d, dst_d, w_d, comm_all_d, rm_d, prev_d,
                    active_at_start, const_d,
                    np.asarray(threshold, dtype=wdt),
                )
            else:
                (src_d, dst_d, w_d, comm_all_d, rm_d, prev_d,
                 gained_d, mod_d, iters_d, nc_d, ne2_d,
                 cq_d, cmoved_d, covf_d) = phase_fn(
                    src_d, dst_d, w_d, comm_all_d, rm_d, prev_d,
                    active_at_start, const_d,
                    np.asarray(threshold, dtype=wdt),
                )
            # THE one device->host sync of this phase: every per-row
            # scalar + the telemetry buffers in a single transfer.
            gained, (mod_h, iters_h, nc_h, ne2_h, cq_h, cmoved_h,
                     covf_h) = _phase_sync(
                gained_d, mod_d, iters_d, nc_d, ne2_d,
                cq_d, cmoved_d, covf_d)
        gained = np.asarray(gained, dtype=bool)
        phase_wall = time.perf_counter() - t1
        n_active = max(int(active_at_start.sum()), 1)
        share = phase_wall / n_active

        traversed = 0
        for i in np.flatnonzero(active_at_start):
            it = int(iters_h[i])
            tot_iters[i] += it
            traversed += int(ne_cur[i]) * it
            pc = decode_phase_conv(phase, it, cq_h[i], cmoved_h[i],
                                   covf_h[i], gained=bool(gained[i]))
            row_conv[i].append(pc)
            if gained[i]:
                row_phases[i].append(PhaseStats(
                    phase=len(row_phases[i]),
                    modularity=float(mod_h[i]), iterations=it,
                    num_vertices=int(nv_cur[i]),
                    num_edges=int(ne_cur[i]), seconds=share))
                nv_cur[i] = int(nc_h[i])
                ne_cur[i] = int(ne2_h[i])
        tracer.count("traversed_edges", traversed)
        active = active_at_start & gained \
            & (tot_iters <= MAX_TOTAL_ITERATIONS)
        if verbose:
            print(f"batched phase {phase}: active {int(active.sum())}/"
                  f"{prep.n_jobs}, iters {iters_h[:prep.n_jobs]}")
        tracer.ledger_snapshot(phase)
        if bucketed_phase:
            # The phase-0 plans are dead weight from here on (coarse
            # phases re-bin on device or run fused); drop the device
            # refs so HBM frees.
            plan_d = None
            # One-notch coarse-class shrink (see _coarse_class): iff
            # every row still clustering fits, the batch drops to the
            # serving-coarse class — the decision reads only the (nc,
            # ne2) scalars this phase's sync already fetched, and the
            # fused phases then sweep/coalesce 4-16x less padding.
            cnv, cne = _coarse_class(cur_nv, cur_ne)
            if active.any() and (cnv, cne) != (cur_nv, cur_ne) \
                    and int(nc_h[active].max()) <= cnv \
                    and int(ne2_h[active].max()) <= cne:
                src_d, dst_d, w_d, rm_d = _shrink_batch(
                    src_d, dst_d, w_d, rm_d, cnv=cnv, cne=cne)
                cur_nv, cur_ne = cnv, cne
                coarse_class = (cnv, cne)
                phase_fn, coarse_engine = _coarse_fn(cnv, cne)
        phase += 1

    # THE final label gather: one O(B * nv_pad) transfer for the whole
    # batch; comm_all rows are already dense (composed through the
    # per-phase device renumber).
    comm_all_h, prev_h = jax.device_get((comm_all_d, prev_d))  # graftlint: disable=R010 — the allowlisted final label gather (batched)
    device_s = time.perf_counter() - t0

    results = []
    for i in range(prep.n_jobs):
        nv = int(prep.nv_real[i])
        results.append(LouvainResult(
            communities=np.asarray(comm_all_h[i, :nv], dtype=np.int64),
            modularity=float(prev_h[i]),
            phases=row_phases[i],
            total_iterations=int(tot_iters[i]),
            total_seconds=sum(p.seconds for p in row_phases[i]),
            convergence=row_conv[i],
        ))
    return BatchResult(
        results=results, wall_s=prep.pack_s + device_s, n_phases=phase,
        b_pad=B, n_jobs=prep.n_jobs, slab_class=prep.slab_class,
        phase_engines=phase_engines, coarse_class=coarse_class,
        pack_s=prep.pack_s, device_s=device_s,
    )


def _execute_subrow(prep: PreparedBatch, *, threshold: float,
                    max_phases: int, tracer=None,
                    verbose: bool = False) -> BatchResult:
    """The EXECUTE half of a packed batch (ISSUE 20): the
    :func:`execute_prepared` phase loop with every per-graph scalar
    widened to ``[B, n_sub]`` — per-SUB-row masked exit, the one-notch
    coarse shrink decided on the MAX over sub-rows still active, and the
    final gather unpacked per fence (labels slice at the sub-row's
    pack-time offset, minus the offset).  One host sync per phase, one
    compiled program per (row class, B, n_sub), re-runnable like the
    plain path."""
    from cuvite_tpu.louvain.driver import (
        LouvainResult,
        PhaseStats,
        _phase_sync,
    )

    if tracer is None:
        from cuvite_tpu.utils.trace import NullTracer

        tracer = NullTracer()

    t0 = time.perf_counter()
    B = prep.b_pad
    n_sub = prep.n_sub
    nv_pad0 = prep.nv_pad
    nv_sub0 = nv_pad0 // n_sub
    cur_nv, cur_ne = nv_pad0, prep.ne_pad
    coarse_class = None
    wdt = np.dtype(np.float32)
    adt = prep.adt
    mesh = prep.mesh

    phase_fn = _get_batched_phase(
        mesh, nv_pad0, adt, MAX_TOTAL_ITERATIONS,
        engine="subrow", n_sub=n_sub)
    src_d, dst_d, w_d = prep.src_d, prep.dst_d, prep.w_d
    rm_d, const_d = prep.rm_d, prep.const_d
    comm_all_d, prev_d = prep.comm_all_d, prep.prev_d

    active = prep.sub_valid.copy()                  # [B, n_sub]

    nv_cur = prep.nv_real.astype(np.int64).copy()   # [B, n_sub]
    ne_cur = prep.ne_real.astype(np.int64).copy()
    tot_iters = np.zeros((B, n_sub), dtype=np.int64)
    sub_phases: list = [[[] for _ in range(n_sub)] for _ in range(B)]
    sub_conv: list = [[[] for _ in range(n_sub)] for _ in range(B)]
    phase_engines: list = []
    phase = 0

    while active.any() and phase < max_phases:
        t1 = time.perf_counter()
        active_at_start = active.copy()
        phase_engines.append("subrow")
        tracer.ledger_phase_begin()
        tracer.track("slab", src_d, dst_d, w_d)
        tracer.track("tables", rm_d, const_d)
        with tracer.stage("iterate"):
            (src_d, dst_d, w_d, comm_all_d, rm_d, prev_d,
             gained_d, mod_d, iters_d, nc_d, ne2_d,
             cq_d, cmoved_d, covf_d) = phase_fn(
                src_d, dst_d, w_d, comm_all_d, rm_d, prev_d,
                active_at_start, const_d,
                np.asarray(threshold, dtype=wdt),
            )
            gained, (mod_h, iters_h, nc_h, ne2_h, cq_h, cmoved_h,
                     covf_h) = _phase_sync(
                gained_d, mod_d, iters_d, nc_d, ne2_d,
                cq_d, cmoved_d, covf_d)
        gained = np.asarray(gained, dtype=bool)     # [B, n_sub]
        phase_wall = time.perf_counter() - t1
        n_active = max(int(active_at_start.sum()), 1)
        share = phase_wall / n_active

        traversed = 0
        for i, s in zip(*np.nonzero(active_at_start)):
            it = int(iters_h[i, s])
            tot_iters[i, s] += it
            traversed += int(ne_cur[i, s]) * it
            pc = decode_phase_conv(phase, it, cq_h[i, s], cmoved_h[i, s],
                                   covf_h[i], gained=bool(gained[i, s]))
            sub_conv[i][s].append(pc)
            if gained[i, s]:
                sub_phases[i][s].append(PhaseStats(
                    phase=len(sub_phases[i][s]),
                    modularity=float(mod_h[i, s]), iterations=it,
                    num_vertices=int(nv_cur[i, s]),
                    num_edges=int(ne_cur[i, s]), seconds=share))
                nv_cur[i, s] = int(nc_h[i, s])
                ne_cur[i, s] = int(ne2_h[i, s])
        tracer.count("traversed_edges", traversed)
        active = active_at_start & gained \
            & (tot_iters <= MAX_TOTAL_ITERATIONS)
        if verbose:
            print(f"packed phase {phase}: active "
                  f"{int(active.sum())}/{prep.n_jobs} sub-rows, "
                  f"iters max {int(iters_h.max())}")
        tracer.ledger_snapshot(phase)
        if phase == 0:
            # One-notch coarse shrink, decided on the MAX over sub-rows
            # still active (ISSUE 20): every fence interval drops to the
            # SUB class's serving-coarse class iff every active sub-row
            # fits — same scalars, same one-binary-decision shape as the
            # plain batched shrink, so a packed batch compiles at most
            # two (class, B, n_sub) programs.
            nv_s, ne_s = cur_nv // n_sub, cur_ne // n_sub
            cnv_s, cne_s = _coarse_class(nv_s, ne_s)
            if active.any() and (cnv_s, cne_s) != (nv_s, ne_s) \
                    and int(nc_h[active].max()) <= cnv_s \
                    and int(ne2_h[active].max()) <= cne_s:
                src_d, dst_d, w_d, rm_d = _shrink_subrow_batch(
                    src_d, dst_d, w_d, rm_d, n_sub=n_sub, nv_sub=nv_s,
                    cnv_sub=cnv_s, cne_sub=cne_s)
                cur_nv, cur_ne = n_sub * cnv_s, n_sub * cne_s
                coarse_class = (cur_nv, cur_ne)
                phase_fn = _get_batched_phase(
                    mesh, cur_nv, adt, MAX_TOTAL_ITERATIONS,
                    engine="subrow", n_sub=n_sub)
        phase += 1

    comm_all_h, prev_h = jax.device_get((comm_all_d, prev_d))  # graftlint: disable=R010 — the allowlisted final label gather (packed batch)
    device_s = time.perf_counter() - t0

    results = []
    for j in range(prep.n_jobs):
        i, s = divmod(j, n_sub)
        nv = int(prep.nv_real[i, s])
        voff = s * nv_sub0
        results.append(LouvainResult(
            communities=np.asarray(
                comm_all_h[i, voff:voff + nv], dtype=np.int64) - voff,
            modularity=float(prev_h[i, s]),
            phases=sub_phases[i][s],
            total_iterations=int(tot_iters[i, s]),
            total_seconds=sum(p.seconds for p in sub_phases[i][s]),
            convergence=sub_conv[i][s],
        ))
    return BatchResult(
        results=results, wall_s=prep.pack_s + device_s, n_phases=phase,
        b_pad=B, n_jobs=prep.n_jobs, slab_class=prep.slab_class,
        phase_engines=phase_engines, coarse_class=coarse_class,
        pack_s=prep.pack_s, device_s=device_s, n_sub=n_sub,
    )


def run_batched(batch: BatchedSlab, *, threshold: float = 1.0e-6,
                max_phases: int = TERMINATION_PHASE_COUNT,
                mesh="auto", tracer=None, verbose: bool = False,
                engine: str = "fused", bucket_shape=None) -> BatchResult:
    """Cluster every row of a packed batch; one compile per
    (class, B, engine), one host sync per phase, one final label gather.
    Composition of the two pipeline halves —
    ``execute_prepared(prepare_batch(batch))`` — so the serial path and
    the pipelined dispatcher run the exact same code (ISSUE 14).

    Per-row semantics match the fused single-shard driver's plain
    schedule at a fixed ``threshold``: phases run until a row's gain
    drops below it (that row masks out), every row's reported Q is its
    last gaining phase's in-loop value.  ``PhaseStats.seconds`` is the
    batch phase wall split evenly over the rows active in that phase —
    per-tenant wall is an AMORTIZED share, which is the serving-truth
    number (the batch really did cost one wall interval).

    ``engine``: ``'fused'`` — every phase through the vmapped fused
    loop; ``'bucketed'`` — phase 0 (the bulk of the per-row edge mass)
    through the vmapped sort-free bucketed step over cross-graph-padded
    plans built at pack time (``batch_bucket_plans``); later phases
    keep the fused loop.  ``bucket_shape`` pins the plan geometry
    (``core.batch.BucketShape``) so many batches share one compiled
    phase-0 program; None derives it from this batch.

    ``mesh``: ``'auto'`` shards the batch axis over the largest usable
    pow2 device count (:func:`make_batch_mesh`); ``None`` pins the
    single-device program; or pass an explicit 1-D ``Mesh`` over
    ``BATCH_AXIS``.  Sharding never changes per-row results — the
    program has no cross-row op — only which device runs which rows.
    """
    prep = prepare_batch(batch, mesh=mesh, engine=engine,
                         bucket_shape=bucket_shape, tracer=tracer)
    return execute_prepared(prep, threshold=threshold,
                            max_phases=max_phases, tracer=tracer,
                            verbose=verbose)


@dataclasses.dataclass
class PreparedMany:
    """A :func:`cluster_many` job set after the PACK stage: the
    edgeless jobs' inline answers plus the uploaded PreparedBatch for
    the rest (None when every job was edgeless).  ``execute_many``
    turns it into the full in-order BatchResult."""

    graphs_nv: list          # num_vertices per input, in order
    edgeless: set            # input indices answered inline
    prep: PreparedBatch | None

    @property
    def pack_s(self) -> float:
        return self.prep.pack_s if self.prep is not None else 0.0


def pack_many(graphs, *, b_pad: int | None = None,
              slab_class: tuple | None = None, mesh="auto",
              engine: str = "fused", bucket_shape=None,
              tracer=None) -> PreparedMany:
    """The PACK stage of :func:`cluster_many`: edgeless split + slab
    stacking + plan build + device upload.  Jax work is upload-only —
    no compiled program runs here, which is what lets the pipelined
    dispatcher overlap this with the previous batch's execution."""
    if tracer is None:
        from cuvite_tpu.utils.trace import NullTracer

        tracer = NullTracer()
    edgeless = {i for i, g in enumerate(graphs) if g.num_edges == 0}
    packed = [g for i, g in enumerate(graphs) if i not in edgeless]
    prep = None
    if packed:
        with tracer.stage("plan"):
            batch = batch_slabs(packed, b_pad=b_pad,
                                slab_class=slab_class)
        prep = prepare_batch(batch, mesh=mesh, engine=engine,
                             bucket_shape=bucket_shape, tracer=tracer)
    return PreparedMany(graphs_nv=[g.num_vertices for g in graphs],
                        edgeless=edgeless, prep=prep)


def pack_subrow_many(graphs, layout, *, b_pad: int | None = None,
                     mesh="auto", tracer=None) -> PreparedMany:
    """The PACK stage of a MERGED batch (ISSUE 20): edgeless split +
    sub-row packing (core/batch.py::pack_subrows) + device upload.
    Returns the same :class:`PreparedMany` handoff unit as
    :func:`pack_many` — ``execute_many`` dispatches on the prepared
    engine, so the pipelined dispatcher runs merged and plain batches
    through identical stages."""
    if tracer is None:
        from cuvite_tpu.utils.trace import NullTracer

        tracer = NullTracer()
    from cuvite_tpu.core.batch import pack_subrows

    edgeless = {i for i, g in enumerate(graphs) if g.num_edges == 0}
    packed_graphs = [g for i, g in enumerate(graphs) if i not in edgeless]
    prep = None
    if packed_graphs:
        with tracer.stage("plan"):
            packed = pack_subrows(packed_graphs, layout, b_pad=b_pad)
        prep = prepare_packed(packed, mesh=mesh, tracer=tracer)
    return PreparedMany(graphs_nv=[g.num_vertices for g in graphs],
                        edgeless=edgeless, prep=prep)


def cluster_packed(graphs, layout, *, threshold: float = 1.0e-6,
                   max_phases: int = TERMINATION_PHASE_COUNT,
                   b_pad: int | None = None, mesh="auto", tracer=None,
                   verbose: bool = False) -> BatchResult:
    """Sub-row-pack small-class graphs and run them as ONE merged batch
    of ``layout.row_class`` rows — the packed analog of
    :func:`cluster_many` (in-order results, edgeless answered inline).
    Per-tenant labels and Q are bit-identical to each graph's B=1 run:
    the fences make every per-run float content-local
    (louvain/subrow.py's module note carries the argument)."""
    pm = pack_subrow_many(graphs, layout, b_pad=b_pad, mesh=mesh,
                          tracer=tracer)
    return execute_many(pm, threshold=threshold, max_phases=max_phases,
                        tracer=tracer, verbose=verbose)


def execute_many(pm: PreparedMany, *, threshold: float = 1.0e-6,
                 max_phases: int = TERMINATION_PHASE_COUNT,
                 tracer=None, verbose: bool = False) -> BatchResult:
    """The EXECUTE stage of :func:`cluster_many`: run the prepared
    batch and reassemble the in-order results list (edgeless jobs
    answered inline, costing no batch rows)."""
    from cuvite_tpu.louvain.driver import LouvainResult

    if pm.prep is not None:
        br = execute_prepared(pm.prep, threshold=threshold,
                              max_phases=max_phases, tracer=tracer,
                              verbose=verbose)
    else:
        br = BatchResult(results=[], wall_s=0.0, n_phases=0, b_pad=0,
                         n_jobs=0, slab_class=(0, 0))
    out = []
    packed_iter = iter(br.results)
    for i, nv in enumerate(pm.graphs_nv):
        if i in pm.edgeless:
            out.append(LouvainResult(
                communities=np.arange(nv, dtype=np.int64),
                modularity=0.0, phases=[], total_iterations=0,
                total_seconds=0.0))
        else:
            out.append(next(packed_iter))
    br.results = out
    return br


def cluster_many(graphs, *, threshold: float = 1.0e-6,
                 max_phases: int = TERMINATION_PHASE_COUNT,
                 b_pad: int | None = None, slab_class: tuple | None = None,
                 mesh="auto", tracer=None, verbose: bool = False,
                 engine: str = "fused", bucket_shape=None) -> BatchResult:
    """Pack same-class graphs and run them as one batch (edgeless graphs
    are answered inline — every vertex its own community, Q = 0 — and
    never enter the packed batch, mirroring louvain_phases).  The
    returned ``results`` list covers EVERY input in order;
    ``n_jobs``/``pack_util``/``jobs_per_s`` describe only the PACKED
    batch (inline-answered edgeless jobs cost no batch rows).
    Composition of :func:`pack_many` + :func:`execute_many` — the two
    stages the pipelined dispatcher runs on separate threads.
    ``engine``/``bucket_shape``: see :func:`run_batched`."""
    if tracer is None:
        from cuvite_tpu.utils.trace import NullTracer

        tracer = NullTracer()
    pm = pack_many(graphs, b_pad=b_pad, slab_class=slab_class, mesh=mesh,
                   engine=engine, bucket_shape=bucket_shape, tracer=tracer)
    return execute_many(pm, threshold=threshold, max_phases=max_phases,
                        tracer=tracer, verbose=verbose)
