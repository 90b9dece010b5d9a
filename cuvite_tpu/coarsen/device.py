"""On-device inter-phase coarsening: distbuildNextLevelGraph in HBM.

The host pipeline (coarsen/rebuild.py — the bit-parity oracle for this
module) runs after every phase: device_get the labels, np.unique
renumber, relabel + coalesce the edge list on the host, rebuild the
DistGraph, re-upload the slab.  Between two phases that is two O(E)
PCIe crossings plus an idle device — the single biggest wall-clock
lever left after the engine work (ISSUE 3; PASCO, arXiv:2412.13592,
measures coarsening as the scalability bottleneck of multilevel
clustering, and the GPU Louvain line keeps aggregation on-accelerator
for the same reason, arXiv:1805.10904).

This module is the device-resident equivalent, all under ``jax.jit``
with static pow2-padded shapes:

  1. ``device_renumber`` — dense renumbering of surviving communities
     (presence scatter + exclusive prefix count over the padded label
     space), matching the reference's sorted-order renumbering
     (rebuild.cpp:167-197: smallest surviving label -> 0) and therefore
     ``rebuild.renumber_communities`` exactly;
  2. ``device_coarsen_slab`` — relabel both endpoints to dense ids and
     coalesce duplicate (src, dst) pairs through THE segmented-coalesce
     chokepoint (ops/segment.py::coalesced_runs, a packed sort;
     graftlint R013 keeps stray slab sorts out), landing the
     coarse graph COMPACTED into a prefix of the SAME slab class: out
     arrays keep the input's [ne_pad] shape, real rows in [0, ne2),
     padding (src == nv_pad, w == 0) after.  Phases whose coarse graph
     still fits the class re-enter the same compiled step — zero
     retraces, zero transfers; the driver drops to a smaller pow2 class
     only when the one-scalar-per-phase host sync (already paid for
     convergence) shows the graph fits, via ``shrink_slab``.

Accumulation: duplicate-run weights sum in ``accum_dtype`` (default:
the weight dtype; ``'ds32'`` = double-single pairs, collapsed to f32
once — the scale-safe mode for self-loop runs whose intra-community
mass exceeds f32's 2^24 integer range).  The host oracle accumulates
f64 and casts once, so device == host bit-for-bit whenever the run
sums are exactly representable (unit/dyadic weights — the parity
suite's domain, tests/test_coarsen_device.py); beyond it the ds32 mode
keeps ~2^-48 relative agreement.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from cuvite_tpu.core.types import next_pow2
from cuvite_tpu.ops import segment as seg


def device_coarsen_enabled() -> bool:
    """Device-resident coarsening is the default; CUVITE_DEVICE_COARSEN=0
    keeps the host pipeline (the A/B lever and the escape hatch).  Read
    per call, not at import, so tests and benches can toggle it."""
    return os.environ.get("CUVITE_DEVICE_COARSEN", "1").lower() \
        not in ("", "0", "false")


@functools.partial(jax.jit, static_argnames=("nv_pad",))
def device_renumber(comm, real_mask, *, nv_pad: int):
    """Dense renumbering of the surviving community labels, on device.

    ``comm``: [nv_pad] labels in the padded vertex id space (every real
    vertex's label is a real vertex id < nv_pad); ``real_mask``: [nv_pad]
    bool.  Returns ``(dense_map, nc)``: ``dense_map[c]`` is the dense id
    of surviving community ``c`` in SORTED label order (smallest -> 0,
    matching np.unique/rebuild.cpp:167-197); entries of labels that
    survive nowhere are meaningless and must never be gathered.  ``nc``
    is the surviving-community count (scalar, stays on device).
    """
    lab = jnp.where(real_mask, comm, nv_pad)
    present = jnp.zeros((nv_pad + 1,), jnp.int32).at[lab].set(1, mode="drop")
    present = present[:nv_pad]  # padding labels land in the dropped slot
    dense_map = (jnp.cumsum(present) - present).astype(comm.dtype)
    return dense_map, jnp.sum(present)


@functools.partial(jax.jit, static_argnames=("nv_pad", "accum_dtype"))
def device_coarsen_slab(src, dst, w, comm, real_mask, *, nv_pad: int,
                        accum_dtype=None, dense_map=None, nc=None):
    """Relabel + coalesce the resident edge slab into the next-phase slab.

    ``src``: [ne_pad] local vertex ids (pad == nv_pad, sorted to the
    tail); ``dst``: [ne_pad] padded-space tail ids (pad == 0, w == 0);
    ``comm``: [nv_pad] phase-end labels; ``real_mask``: [nv_pad] bool.

    Returns ``(src2, dst2, w2, dense_map, nc, ne2)``: the coarse slab in
    the SAME [ne_pad] class, coalesced rows sorted by (src, dst) and
    compacted into [0, ne2), padding (src == nv_pad, dst == 0, w == 0)
    after; ``dense_map``/``nc`` as :func:`device_renumber`.  Intra-
    community weight collapses onto the diagonal as self-loops
    (rebuild.cpp:244-279), which keeps modularity consistent across
    phases.  ``accum_dtype``: run-sum accumulator — None (weight dtype),
    a dtype name, or ``'ds32'`` for double-single pairs.  ``dense_map``/
    ``nc`` (pass both or neither): a precomputed :func:`device_renumber`
    of the SAME ``(comm, real_mask)`` — the fused driver reuses the one
    it already ran for label composition instead of renumbering twice.
    """
    wdt = w.dtype
    if dense_map is None:
        dense_map, nc = device_renumber(comm, real_mask, nv_pad=nv_pad)

    pad = src >= nv_pad
    safe_src = jnp.minimum(src, nv_pad - 1)
    csrc = jnp.take(dense_map, jnp.take(comm, safe_src))
    cdst = jnp.take(dense_map, jnp.take(comm, dst))
    new_src = jnp.where(pad, jnp.asarray(nv_pad, src.dtype),
                        csrc.astype(src.dtype))
    new_dst = jnp.where(pad, jnp.zeros((), dst.dtype),
                        cdst.astype(dst.dtype))
    w_in = jnp.where(pad, jnp.zeros_like(w), w)

    src2, dst2, w2, ne2 = seg.coalesced_runs(
        new_src, new_dst, w_in, nv_pad=nv_pad, accum_dtype=accum_dtype)
    w2 = w2.astype(wdt)
    return src2, dst2, w2, dense_map, nc, ne2


@functools.partial(jax.jit, static_argnames=("nv_pad",))
def device_weighted_degrees(src, w, *, nv_pad: int):
    """vDegree of a device-resident slab (padding src >= nv_pad drops)."""
    return seg.segment_sum(w, src, num_segments=nv_pad, sorted_ids=True)


@jax.jit
def device_compose_labels(dense_map, labels, comm_all):
    """Cross-phase label composition on device (main.cpp:374-403):
    original vertex -> current dense vertex id, through this phase's
    padded-space ``labels`` and its ``dense_map``."""
    return jnp.take(dense_map, jnp.take(labels, comm_all))


# --- batched (multi-tenant) lifts, ISSUE 9 ---------------------------------
# The batched driver (louvain/batched.py) runs B same-class graphs
# through one compiled program with a leading batch axis; these are the
# vmap lifts of the device coarsener it embeds.  They are plain
# traceable functions (the inner jits inline under the caller's jit):
# jitting here would fragment the driver's one-program-per-phase
# property into per-helper dispatches.

def batched_renumber(comm, real_mask, *, nv_pad: int):
    """[B, nv_pad] lift of :func:`device_renumber`: per-row dense maps
    and surviving-community counts ``(dense_map [B, nv_pad], nc [B])``."""
    return jax.vmap(
        functools.partial(device_renumber, nv_pad=nv_pad))(comm, real_mask)


def batched_compose_labels(dense_map, labels, comm_all):
    """[B, ...] lift of :func:`device_compose_labels`."""
    return jax.vmap(device_compose_labels)(dense_map, labels, comm_all)


def batched_coarsen_slab(src, dst, w, comm, real_mask, dense_map, nc, *,
                         nv_pad: int, accum_dtype=None):
    """[B, ne_pad] lift of :func:`device_coarsen_slab` (precomputed
    per-row ``dense_map``/``nc`` required — the batched driver always
    has them from the label composition)."""

    def one(s, d, ww, c, rm, dm, n):
        return device_coarsen_slab(
            s, d, ww, c, rm, nv_pad=nv_pad, accum_dtype=accum_dtype,
            dense_map=dm, nc=n)

    return jax.vmap(one)(src, dst, w, comm, real_mask, dense_map, nc)


# --- sub-row (fenced) lifts, ISSUE 20 --------------------------------------
# A packed row (core/batch.py::SubRowLayout) holds n_sub disjoint graphs
# at fixed vertex offsets; its coarsening must renumber SEGMENT-LOCALLY
# so every sub-row's coarse ids stay inside its own fence interval —
# whole-row dense ranks would blur the seams for the next phase.  Two
# maps come out of one presence scan: the CURRENT-offset map relabels
# the resident slab (whose class may have shrunk), the ORIGINAL-offset
# map composes the cross-phase labels, which therefore always live in
# the pack-time offset space — unpack is a fence slice minus the
# offset, no matter when each sub-row retired or whether the slab
# shrank in between.


@functools.partial(jax.jit, static_argnames=("nv_pad", "n_sub", "nv_sub0"))
def subrow_renumber(comm, real_mask, *, nv_pad: int, n_sub: int,
                    nv_sub0: int):
    """Segment-local dense renumbering of a packed row's surviving
    communities.  Returns ``(dmap_cur, dmap_orig, nc)``: ``dmap_cur[c]``
    is community ``c``'s dense id at CURRENT sub-row offsets
    (``s * (nv_pad // n_sub) + rank``), ``dmap_orig[c]`` the same rank
    at ORIGINAL offsets (``s * nv_sub0 + rank``), ``nc`` the ``[n_sub]``
    per-sub-row surviving counts.  Ranks are the within-segment cumsum
    of the same presence scan :func:`device_renumber` uses, so each
    sub-row's ranks equal its solo run's (smallest label -> 0)."""
    lab = jnp.where(real_mask, comm, nv_pad)
    present = jnp.zeros((nv_pad + 1,), jnp.int32).at[lab].set(1, mode="drop")
    present = present[:nv_pad].reshape(n_sub, -1)
    local = jnp.cumsum(present, axis=-1) - present
    nv_sub = nv_pad // n_sub
    offs_cur = (jnp.arange(n_sub, dtype=jnp.int32) * nv_sub)[:, None]
    offs_orig = (jnp.arange(n_sub, dtype=jnp.int32) * nv_sub0)[:, None]
    dmap_cur = (local + offs_cur).reshape(nv_pad).astype(comm.dtype)
    dmap_orig = (local + offs_orig).reshape(nv_pad).astype(comm.dtype)
    return dmap_cur, dmap_orig, jnp.sum(present, axis=-1)


@functools.partial(jax.jit, static_argnames=("nv_pad", "n_sub", "nv_sub0"))
def subrow_compose_labels(dmap_orig, labels, comm_all, *, nv_pad: int,
                          n_sub: int, nv_sub0: int):
    """Cross-phase label composition for a packed row: ``comm_all``
    holds ORIGINAL-offset dense ids; map them to current offsets (the
    slab class may have shrunk), gather this phase's ``labels``, then
    back to original offsets through ``dmap_orig``.  Gathers clamp —
    retired sub-rows' stale ids may exceed the shrunken segment, and
    their positions are masked out by the caller anyway."""
    nv_sub = nv_pad // n_sub
    s = comm_all // nv_sub0
    r = comm_all % nv_sub0
    v_cur = jnp.minimum(s, n_sub - 1) * nv_sub + jnp.minimum(r, nv_sub - 1)
    v_cur = jnp.minimum(v_cur, nv_pad - 1)
    return jnp.take(dmap_orig, jnp.take(labels, v_cur))


def batched_subrow_renumber(comm, real_mask, *, nv_pad: int, n_sub: int,
                            nv_sub0: int):
    """[B, nv_pad] lift of :func:`subrow_renumber`."""
    return jax.vmap(functools.partial(
        subrow_renumber, nv_pad=nv_pad, n_sub=n_sub, nv_sub0=nv_sub0))(
        comm, real_mask)


def batched_subrow_compose(dmap_orig, labels, comm_all, *, nv_pad: int,
                           n_sub: int, nv_sub0: int):
    """[B, ...] lift of :func:`subrow_compose_labels`."""
    return jax.vmap(functools.partial(
        subrow_compose_labels, nv_pad=nv_pad, n_sub=n_sub,
        nv_sub0=nv_sub0))(dmap_orig, labels, comm_all)


def shrink_slab(src, dst, w, *, new_nv_pad: int, new_ne_pad: int):
    """Drop a compacted coarse slab to a smaller pow2 class — device ops
    only (a prefix slice plus a padding-sentinel rewrite; real ids are
    < nc <= new_nv_pad, so only the old nv_pad sentinels move)."""
    s = src[:new_ne_pad]
    s = jnp.where(s >= new_nv_pad, jnp.asarray(new_nv_pad, s.dtype), s)
    return s, dst[:new_ne_pad], w[:new_ne_pad]


@functools.partial(jax.jit,
                   static_argnames=("nv_pad", "new_nv_pad", "new_ne_pad"))
def grow_slab(src, dst, w, *, nv_pad: int, new_nv_pad: int,
              new_ne_pad: int):
    """Lift a canonical slab to a LARGER pow2 class — the spill twin of
    :func:`shrink_slab`, device ops only (a sentinel rewrite plus a
    sentinel-padded extend).  The streaming delta path (stream/delta.py)
    uses it when an insert batch overflows the resident class's padding
    headroom; real rows keep their prefix order, so the grown slab is
    still canonical."""
    cur_ne_pad = src.shape[0]  # static under jit
    if new_nv_pad < nv_pad or new_ne_pad < cur_ne_pad:
        raise ValueError("grow_slab grows classes; use shrink_slab to drop")
    pad_n = new_ne_pad - cur_ne_pad
    s = jnp.where(src >= nv_pad, jnp.asarray(new_nv_pad, src.dtype), src)
    s = jnp.concatenate([s, jnp.full((pad_n,), new_nv_pad, src.dtype)])
    d = jnp.concatenate([dst, jnp.zeros((pad_n,), dst.dtype)])
    ww = jnp.concatenate([w, jnp.zeros((pad_n,), w.dtype)])
    return s, d, ww


def maybe_shrink_to_class(src, dst, w, *, nc: int, ne2: int, nv_pad: int,
                          ne_pad: int, min_nv_pad: int = 4096,
                          min_ne_pad: int = 16384):
    """THE slab-class transition policy, shared by the sort-engine and
    fused drivers (one copy, so their padding behavior cannot drift):
    recompute the pow2 class for a coarse graph (same floors as
    DistGraph.build's single-shard defaults, so device and host rebuilds
    land on identical compiled-step cache keys) and shrink the slab only
    when a strictly smaller class fits — coarsening never grows nv/ne,
    so the class never grows.  Returns (src, dst, w, nv_pad, ne_pad)."""
    new_nv_pad = max(next_pow2(max(nc, 1)), min_nv_pad)
    new_ne_pad = max(next_pow2(max(ne2, 1)), min_ne_pad)
    if new_nv_pad < nv_pad or new_ne_pad < ne_pad:
        src, dst, w = shrink_slab(src, dst, w, new_nv_pad=new_nv_pad,
                                  new_ne_pad=new_ne_pad)
        return src, dst, w, new_nv_pad, new_ne_pad
    return src, dst, w, nv_pad, ne_pad
