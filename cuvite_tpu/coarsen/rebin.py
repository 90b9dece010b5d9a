"""Device re-binning: bucket plans built ON DEVICE from a coalesced
slab (ISSUE 19 tentpole a).

The degree-bucketed engine's plans (louvain/bucketed.py::BucketPlan)
were host-built every phase: coarse phases of the per-graph driver pay
a host pass + plan upload per phase, and the batched serving path
(louvain/batched.py) downgraded every coarse phase to the FUSED engine
— a packed 2-channel ``lax.sort`` per iteration — because re-binning
needed a host histogram.  GPU Louvain gets its coarse-phase throughput
precisely by keeping per-phase neighbor aggregation in binned form
rather than re-sorting (Naim et al., arXiv:1805.10904), and the
reference's heuristics assume cheap per-phase rebinning (Ghosh et al.,
arXiv:1410.1237).  This module is the TPU translation: a pure-jnp,
jittable, vmappable plan builder — degree histogram over the padded
label space, per-width class assignment against the static
``DEFAULT_BUCKETS`` ladder, gather-index construction into the stacked
``[rows, width]`` dst/w layout ``bucketed_step`` already consumes —
with NO host sync and NO ``lax.sort`` (this module sits inside
graftlint R013's no-sort scope).

Geometry.  :func:`rebin_plan` takes its bucket shapes as a static
``((width, rows), ...)`` tuple, and two callers fill it differently.

:func:`rebin_geometry` derives a CLASS-static shape: every
truncated-ladder width is kept (an empty class is all-padding rows),
and class k's row count is the provable occupancy ceiling

    rows_k = pow2_ceil(min(nv_pad, ne_pad // (prev_k + 1)))

— a vertex in class k has degree > prev_k, so at most
ne_pad // (prev_k + 1) vertices fit the class, and pow2_ceil dominates
the host builder's pow2 ``nb_pad`` (pow2_ceil is monotone), so every
host bucket embeds as the device bucket's prefix.  One program per
``(nv_pad, ne_pad)`` slab class, exactly like the slab kernels.  The
batched serving path uses it because it traces the plan inside one
vmapped program per ``(class, B)``: it sees no histogram without a
host sync, and its compile keys must stay stable across tenants.  The
per-graph driver uses it at the floor slab class too, where the
ceiling is a few hundred thousand slots and one program shared by
every small tail phase is worth more than the padding it sweeps.

:func:`sized_geometry` gives each non-empty class the host builder's
``nb_pad`` rows, from the phase's own degree histogram.  The per-graph
driver uses it above the floor class: it coarsens on the host and
holds the coarse CSR, so the histogram is one O(V) pass over its
offsets, and the class ceiling there pads a phase's plan 10-20x over
what the phase holds (every slot of which the phase loop sweeps each
iteration).  The device plan then has the host plan's shapes exactly.

Eligibility (:func:`rebin_eligible`).  A coalesced slab's max degree is
bounded by nv_pad (distinct neighbors), so nv_pad <= DEFAULT_BUCKETS[-1]
guarantees NO heavy residual — the heavy triple is the host builder's
8-slot all-padding placeholder, statically.  Classes past the ladder
top (nv_pad > 8192, where a heavy residual could exist) and geometries
past the plan-element budget (CUVITE_REBIN_MAX_ELEMS) fall back to the
host ``BucketPlan.build`` oracle, which stays the bit-identity
reference for everything this module emits.

Slab contract: sorted by src with the real rows compacted into the
prefix and padding (src == nv_pad, w == 0) after — what
``DistGraph.build`` CSR expansion, ``coalesced_runs`` output and the
batched coarsen/shrink all guarantee.  Weights are emitted in the slab
weight dtype with NO content-dependent uint8 compression (the
stable-compile-key convention of core/batch.py::batch_bucket_plans);
the self-loop scatter accumulates in the weight dtype, so device ==
host bit-for-bit on the exactness domain (unit/dyadic weights, the
same contract as coarsen/device.py).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from cuvite_tpu.louvain.bucketed import DEFAULT_BUCKETS

# Plan-element ceiling (sum of rows_k * width_k over the geometry): at
# the serving class (4096, 16384) the static geometry costs ~26x ne_pad
# elements — a few MB — but a pathological nv_pad/ne_pad ratio could
# inflate it, so eligibility is budget-gated like every other device
# structure.
DEFAULT_REBIN_MAX_ELEMS = 1 << 27


def rebin_max_elems() -> int:
    from cuvite_tpu.utils.envknob import env_int

    return env_int("CUVITE_REBIN_MAX_ELEMS", DEFAULT_REBIN_MAX_ELEMS,
                   maximum=1 << 34)


def device_rebin_enabled() -> bool:
    """Device re-binning is the default for eligible coarse phases;
    CUVITE_DEVICE_REBIN=0 pins the host BucketPlan.build path (the A/B
    lever and the escape hatch).  Read per call, not at import, so
    tests and benches can toggle it."""
    return os.environ.get("CUVITE_DEVICE_REBIN", "1").lower() \
        not in ("", "0", "false")


def rebin_geometry(nv_pad: int, ne_pad: int,
                   widths: tuple = DEFAULT_BUCKETS) -> tuple:
    """The CLASS-static bucket geometry: ``((width, rows), ...)`` for
    every ladder width kept after truncation (widths whose predecessor
    already covers nv_pad carry no vertex and are dropped — degree is
    bounded by nv_pad on a coalesced slab).  ``rows`` is the pow2
    occupancy ceiling per class; see the module docstring for the
    bound."""
    geom = []
    prev = 0
    for width in widths:
        if prev >= nv_pad:
            break
        cap = min(nv_pad, max(ne_pad // (prev + 1), 1))
        rows = 1 << max(int(cap - 1).bit_length(), 0)
        geom.append((width, rows))
        prev = width
    return tuple(geom)


def sized_geometry(degrees, nv_pad: int,
                   widths: tuple = DEFAULT_BUCKETS) -> tuple:
    """The phase-sized bucket geometry: ``((width, rows), ...)`` for the
    ladder classes that hold at least one vertex, ``rows`` the host
    builder's ``nb_pad`` (``BucketPlan.build``: pow2 ceiling of the
    class's vertex count, 1 for a single vertex), so the device plan
    has the host plan's shapes.  ``degrees``: per-vertex edge counts of
    the slab's label space (at most ``nv_pad`` entries, e.g. the CSR
    offsets' differences).  A degree past the ladder top would be a
    heavy residual, which the device plan does not hold: refused."""
    deg = np.asarray(degrees)
    if len(deg) > nv_pad:
        raise ValueError(f"{len(deg)} degrees for nv_pad {nv_pad}")
    deg = deg[deg > 0]
    if len(deg) and int(deg.max()) > widths[-1]:
        raise ValueError(f"degree {int(deg.max())} past the ladder top "
                         f"{widths[-1]}: a heavy residual needs the host "
                         "plan")
    counts = np.bincount(np.searchsorted(widths, deg, side="left"),
                         minlength=len(widths))
    return tuple((width, 1 << int(n - 1).bit_length())
                 for width, n in zip(widths, counts.tolist()) if n)


def rebin_eligible(nv_pad: int, ne_pad: int,
                   widths: tuple = DEFAULT_BUCKETS) -> bool:
    """True when the class can be re-binned on device with NO heavy
    residual and a bounded plan: nv_pad within the ladder top (max
    coalesced degree <= nv_pad <= widths[-1], so the last kept width
    covers every vertex) and the static geometry within the element
    budget."""
    if nv_pad > widths[-1]:
        return False  # a heavy residual could exist: host oracle path
    geom = rebin_geometry(nv_pad, ne_pad, widths)
    elems = sum(r * w for w, r in geom)
    return elems <= rebin_max_elems()


def rebin_plan(src, dst, w, *, nv_pad: int, base: int, geometry: tuple):
    """Pure-jnp plan builder — trace-safe under jit AND vmap (the
    batched rebinned phase maps it over the tenant axis).

    ``src``: [ne_pad] local vertex ids, sorted, real rows compacted into
    the prefix, padding == nv_pad; ``dst``: [ne_pad] padded-space tail
    ids (padding 0, w 0); ``base``: the shard's first global id (self-
    loop detection, same convention as ``BucketPlan.build``).

    ``geometry``: ``((width, rows), ...)`` in ladder order, from
    :func:`rebin_geometry` or :func:`sized_geometry`.  Class k takes the
    vertices of degree in ``(prev, width]``, ``prev`` the geometry's
    previous width; a geometry that leaves out a ladder class no vertex
    falls in keeps that test right for the classes it keeps, since no
    vertex has a degree in the interval it drops.  ``rows`` must hold
    the class's vertices (the scatter drops any past it).

    Returns ``(buckets, heavy, self_loop, perm)``: ``buckets`` a tuple
    of ``(verts [R], dmat [R, W], wmat [R, W])`` triples in geometry
    (ladder) order — padding rows carry verts == nv_pad, dmat/wmat 0;
    padding COLUMNS of real rows carry the vertex's own global id with
    weight 0, exactly like the host builder — ``heavy`` the static
    8-slot all-padding triple (eligibility proved no residual),
    ``self_loop`` [nv_pad] per-vertex self-loop weight, and ``perm``
    [nv_pad] int32 vertex -> position in the concatenated bucket-row
    space (no-bucket vertices -> the trailing default slot), the
    ``build_assemble_perm`` contract.
    """
    ne_pad = src.shape[0]
    vdt = src.dtype
    ddt = dst.dtype
    wdt = w.dtype
    real = src < nv_pad
    src_i = jnp.where(real, src, nv_pad).astype(jnp.int32)

    # Degree histogram over the padded label space (padding ids drop via
    # the out-of-range segment) + exclusive prefix = CSR row starts of
    # the already-sorted slab.
    deg = jax.ops.segment_sum(real.astype(jnp.int32), src_i,
                              num_segments=nv_pad,
                              indices_are_sorted=True)
    row_start = jnp.cumsum(deg) - deg  # int32: ne_pad <= SLAB_NE_MAX

    is_self = real & (dst == (src_i + jnp.int32(base)).astype(ddt))
    self_loop = jax.ops.segment_sum(
        jnp.where(is_self, w, jnp.zeros_like(w)), src_i,
        num_segments=nv_pad, indices_are_sorted=True).astype(wdt)

    total = sum(r for _, r in geometry)
    vids = jnp.arange(nv_pad, dtype=jnp.int32)
    perm = jnp.full((nv_pad,), total, jnp.int32)
    buckets = []
    off = 0
    prev = 0
    for width, rows in geometry:
        in_cls = (deg > prev) & (deg <= width)
        # Ascending-id compaction (== np.nonzero order of the host
        # builder): scatter each class vertex to its prefix position.
        pos = jnp.cumsum(in_cls.astype(jnp.int32)) - 1  # graftlint: width-ok=cumsum over the [nv_pad] class mask and rebin_eligible caps nv_pad <= DEFAULT_BUCKETS[-1] = 8192
        verts = jnp.full((rows,), nv_pad, jnp.int32).at[
            jnp.where(in_cls, pos, rows)].set(vids, mode="drop")
        row_real = verts < nv_pad
        safe_v = jnp.minimum(verts, nv_pad - 1)
        cols = jnp.arange(width, dtype=jnp.int32)
        idx = jnp.minimum(row_start[safe_v][:, None] + cols[None, :],
                          ne_pad - 1)
        has = (cols[None, :] < deg[safe_v][:, None]) & row_real[:, None]
        own = (verts + jnp.int32(base)).astype(ddt)[:, None]
        dmat = jnp.where(has, dst[idx],
                         jnp.where(row_real[:, None], own,
                                   jnp.zeros((), ddt)))
        wmat = jnp.where(has, w[idx], jnp.zeros((), wdt))
        buckets.append((verts.astype(vdt), dmat, wmat))
        perm = jnp.where(in_cls, jnp.int32(off) + pos, perm)  # graftlint: width-ok=off + pos < total plan rows, and rebin_eligible caps total plan ELEMENTS at REBIN_MAX_ELEMS < 2^31
        off += rows
        prev = width

    heavy = (jnp.full((8,), nv_pad, vdt), jnp.zeros((8,), ddt),
             jnp.zeros((8,), wdt))
    return tuple(buckets), heavy, self_loop, perm


@functools.partial(jax.jit,
                   static_argnames=("nv_pad", "base", "geometry"))
def device_rebin_plan(src, dst, w, *, nv_pad: int, base: int,
                      geometry: tuple):
    """The jitted eager entry point (per-graph driver): one device
    dispatch per phase, statics = the geometry (one program per slab
    class at the floor, where it comes from :func:`rebin_geometry`; one
    per sized geometry above it).  The batched path traces
    :func:`rebin_plan` directly inside its phase program instead."""
    return rebin_plan(src, dst, w, nv_pad=nv_pad, base=base,
                      geometry=geometry)
