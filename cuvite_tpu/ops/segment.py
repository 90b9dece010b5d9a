"""Segment-reduction primitives for edge-parallel graph kernels.

The reference's per-vertex hash maps (distBuildLocalMapCounter,
/root/reference/louvain.cpp:2384-2431) and its GPU dense-scratch dedup kernels
(/root/reference/louvain_cuda.cu:878-1346) both compute the same thing: for
every vertex, the total edge weight into each distinct neighbor community.
On TPU the idiomatic formulation is a lexicographic sort of the edge slab by
``(source vertex, neighbor community)`` followed by run-detection and
segment sums — everything static-shape, everything fused by XLA.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

# CUVITE_DEBUG_BOUNDS is sampled ONCE, at import time: the bound check is
# baked into traced step functions that are cached process-wide
# (driver._STEP_CACHE keys don't include it), so flipping the env var
# after the first compile could never take effect anyway.  Set it before
# importing cuvite_tpu (i.e. before the first compile) or it is ignored.
DEBUG_BOUNDS = os.environ.get("CUVITE_DEBUG_BOUNDS", "0").lower() \
    not in ("", "0", "false")


def segment_sum(data, segment_ids, num_segments, sorted_ids=False):
    return jax.ops.segment_sum(
        data, segment_ids, num_segments=num_segments,
        indices_are_sorted=sorted_ids,
    )


def segment_max(data, segment_ids, num_segments, sorted_ids=False):
    return jax.ops.segment_max(
        data, segment_ids, num_segments=num_segments,
        indices_are_sorted=sorted_ids,
    )


def segment_min(data, segment_ids, num_segments, sorted_ids=False):
    return jax.ops.segment_min(
        data, segment_ids, num_segments=num_segments,
        indices_are_sorted=sorted_ids,
    )


def spmd_env(comm_local, axis_name):
    """Shared SPMD plumbing for the Louvain engines: returns
    ``(comm_full, gsum)`` — the (all_gather'ed) full community vector and the
    cross-shard scalar/array reduction.  Single-shard (``axis_name=None``)
    degenerates to identity."""
    if axis_name is None:
        return comm_local, lambda x: x
    comm_full = jax.lax.all_gather(comm_local, axis_name, tiled=True)  # graftlint: replicated-ok=scope=ici; the replicated exchange's community vector — flat-mesh-only (the hybrid driver rejects exchange='replicated'), so the gather never spans more than one ICI group; the sparse/two-level exchanges are the fix past the cutover
    return comm_full, lambda x: jax.lax.psum(x, axis_name)


# Sentinel accum_dtype selecting double-single (f32-pair) accumulation for
# the in-loop modularity sums — the scale-safe mode for graphs whose 2m
# makes plain f32 reductions eat the 1e-6 convergence threshold (see
# cuvite_tpu/ops/exactsum.py and driver.DS_MIN_TOTAL_WEIGHT).
DS_ACCUM = "ds32"

# Widest edge slab one device call may carry: the run-id/compaction
# cumsums below count slab rows in int32, whose ceiling is 2^31 - 1 —
# and a 2^30-row slab is already ~48 GB of operand HBM, past any single
# chip.  Billion-edge graphs (Friendster's 3.6 B directed rows pad to
# 2^32) MUST arrive pre-sharded into <= SLAB_NE_MAX slabs; the guard
# fails loud instead of wrapping into wrong labels.  widthcheck (R026/
# R028) reads this raise-guard as the eligibility predicate bounding
# ne_pad, and tools/width_audit.py proves the one-past-boundary class
# raises (W002).
SLAB_NE_MAX = 1 << 30


def modularity_terms(counter0, comm_deg, constant, gsum, accum_dtype,
                     axis_name=None):
    """Q = e·c − a²·c² from the per-vertex current-community weights and the
    (already globally reduced) community degrees
    (cf. distComputeModularity, /root/reference/louvain.cpp:2433-2481).

    ``accum_dtype=DS_ACCUM`` accumulates both big reductions in
    double-single f32 pairs (error O(log n * 2^-48) instead of the plain
    tree sum's O(log n * 2^-24)) and collapses to one f32 at the end —
    the in-loop analog of the reference's C++ double accumulation
    (louvain.cpp:2433-2481).  ``axis_name`` is required in SPMD ds mode
    (the cross-shard pair reduction must stay exact; ``gsum`` alone would
    re-lose the low words)."""
    if accum_dtype == DS_ACCUM:
        from cuvite_tpu.ops import exactsum as ds

        le = ds.ds_tree_sum(counter0)
        if axis_name is not None:
            le = ds.ds_psum(le, axis_name)
        # comm_deg is globally replicated after gsum: no cross-shard reduce;
        # square each entry exactly (two_prod) before the pair tree-sum.
        p, e = ds.two_prod(comm_deg, comm_deg)
        la2 = ds.ds_tree_sum(p, e)
        c = ds.ds_from_f32(constant)
        q = ds.ds_add(ds.ds_mul(le, c),
                      ds.ds_neg(ds.ds_mul(la2, ds.ds_mul(c, c))))
        return q[0] + q[1]
    acc = counter0.dtype if accum_dtype is None else accum_dtype
    le_xx = gsum(jnp.sum(counter0.astype(acc)))
    # comm_deg is globally replicated after gsum: no second psum.
    la2_x = jnp.sum(jnp.square(comm_deg.astype(acc)))
    c_acc = constant.astype(acc)
    return le_xx * c_acc - la2_x * c_acc * c_acc


def sort_edges_by_vertex_comm(src, ckey, w, *extras, src_bound=None,
                              key_bound=None):
    """Sort of the edge slab by (src, ckey), stable.

    Returns (src_s, ckey_s, w_s, *extras_s) — any ``extras`` arrays are
    co-sorted as additional payload channels (used by the sparse exchange to
    carry per-slot community degree/size).  Padding edges carry src == nv_pad
    (max segment id) and therefore sort to the tail of the slab.

    With static ``src_bound``/``key_bound`` (exclusive maxima) the two keys
    are packed into ONE integer key ``(src << kbits) | ckey`` — int32 when
    it fits, else int64 — replacing the two-operand lexicographic
    comparator (measured 4-5x faster for the row sorts on TPU).  Equal
    packed keys are exactly equal (src, ckey) pairs and the sort is stable
    either way, so results are bit-identical to the lexicographic path.

    INVARIANT: every src must be < src_bound and every ckey < key_bound,
    or packing corrupts the order (an overflowing ckey bleeds into src's
    bits; at kbits+sbits == 31 the int32 sign bit flips and the row sorts
    to the FRONT).  Callers pass src_bound = nv_local + 1 (padding rows
    carry src == nv_local) and key_bound = nv_total (community ids live in
    padded vertex space).  Set CUVITE_DEBUG_BOUNDS=1 BEFORE the first
    import/compile to verify at runtime (host callback per sort —
    test/debug builds only; the flag is read once at module import into
    DEBUG_BOUNDS, because traced steps are cached process-wide).
    """
    if src_bound is not None and key_bound is not None:
        if DEBUG_BOUNDS:
            def _check(smax, kmax):
                if int(smax) >= int(src_bound) or int(kmax) >= int(key_bound):
                    raise AssertionError(
                        f"packed-sort bound violation: max src {int(smax)} "
                        f"(bound {src_bound}), max ckey {int(kmax)} "
                        f"(bound {key_bound})")

            jax.debug.callback(_check, jnp.max(src), jnp.max(ckey))
        kbits = max(int(key_bound) - 1, 1).bit_length()
        sbits = max(int(src_bound) - 1, 1).bit_length()
        # int64 packing needs jax_enable_x64 (int64 silently degrades to
        # int32 otherwise, corrupting keys); int32 packing is always safe.
        fits32 = kbits + sbits <= 31
        if fits32 or (kbits + sbits <= 63 and jax.config.jax_enable_x64):
            # int64 is legal here BY CONSTRUCTION: the branch above only
            # admits it under jax_enable_x64 (the oracle mode), never in
            # the 32-bit graph mode R003 protects.
            pdt = jnp.int32 if fits32 else jnp.int64  # graftlint: disable=R003
            packed = (src.astype(pdt) << kbits) | ckey.astype(pdt)
            out = jax.lax.sort((packed,) + (w,) + extras, num_keys=1)
            k_s = out[0]
            src_s = (k_s >> kbits).astype(src.dtype)
            ckey_s = (k_s & ((1 << kbits) - 1)).astype(ckey.dtype)
            return (src_s, ckey_s) + tuple(out[1:])
    return jax.lax.sort((src, ckey, w) + extras, num_keys=2)


def coalesced_runs(src, ckey, w, *, nv_pad, accum_dtype=None):
    """Segmented coalesce of an edge slab by (src, ckey): one output row
    per distinct real (src, ckey) pair, rows in ascending (src, ckey)
    order COMPACTED into the slab prefix, duplicate weights summed.

    Same (src, ckey, w) operand convention as
    :func:`sort_edges_by_vertex_comm` — real ids < ``nv_pad`` (pow2),
    padding rows carry src == nv_pad and w == 0 — but the contract is the
    COALESCED result, not a sorted copy.  THE sanctioned coalesce
    chokepoint (graftlint R013 allows no other full-slab sort in coarsen/
    or kernels/): stable sort via :func:`sort_edges_by_vertex_comm`
    (src_bound = nv_pad + 1, key_bound = nv_pad), run detection, run sums
    in ``accum_dtype`` (None = weight dtype; ``'ds32'`` = double-single
    pairs collapsed to f32 once), emit at run-last positions.  Run
    PRESENCE decides the emitted rows, so real zero-weight edges survive.

    Returns ``(src_c, ckey_c, w_c, n)``: [ne_pad]-shaped arrays with real
    rows in [0, n) and padding (src == nv_pad, ckey == 0, w == 0) after.
    """
    ne_pad = src.shape[0]
    if ne_pad > SLAB_NE_MAX:
        raise ValueError(
            f"coalesced_runs: slab has {ne_pad} rows, over SLAB_NE_MAX "
            f"= {SLAB_NE_MAX}: the int32 run-id/compaction cumsums "
            "would overflow (wrong labels, not a crash) — shard the "
            "slab below the ceiling first")
    # Dense ids are < nv_pad; padding src == nv_pad sorts to the tail.
    src_s, ckey_s, w_s = sort_edges_by_vertex_comm(
        src, ckey, w, src_bound=nv_pad + 1, key_bound=nv_pad)
    wdt = w_s.dtype
    starts = run_starts(src_s, ckey_s)
    run_id = jnp.cumsum(starts.astype(jnp.int32)) - 1
    if accum_dtype == DS_ACCUM:
        # Double-single run sums (ops/exactsum.py): exact integer mass up
        # to ~2^48 — self-loop runs of benchmark-scale communities exceed
        # f32's 2^24 long before they exceed this.  One f32 collapse at
        # the end, like the host oracle's single f64 -> f32 cast.
        from cuvite_tpu.ops import exactsum as ds

        hi, lo, last = ds.ds_segment_sums_sorted(run_id, w_s)
        run_w = (hi + lo).astype(wdt)
    else:
        acc = wdt if accum_dtype is None else accum_dtype
        sums = segment_sum(w_s.astype(acc), run_id,
                           num_segments=ne_pad, sorted_ids=True)
        run_w = jnp.take(sums, run_id).astype(wdt)
        last = jnp.concatenate(
            [(src_s[1:] != src_s[:-1]) | (ckey_s[1:] != ckey_s[:-1]),
             jnp.ones((1,), bool)])

    # Emit one row per run, at the run's LAST position (where the ds sum
    # lives); runs are contiguous, so run order — and hence the compacted
    # output order — is the sorted (src, ckey) order either way.
    emit = last & (src_s < nv_pad)
    n = jnp.sum(emit.astype(jnp.int32))
    pos = jnp.cumsum(emit.astype(jnp.int32)) - 1
    slot = jnp.where(emit, pos, ne_pad)  # non-emitted rows drop
    src_c = jnp.full((ne_pad,), nv_pad, src_s.dtype).at[slot].set(
        src_s, mode="drop")
    ckey_c = jnp.zeros((ne_pad,), ckey_s.dtype).at[slot].set(
        ckey_s, mode="drop")
    w_c = jnp.zeros((ne_pad,), wdt).at[slot].set(run_w, mode="drop")
    return src_c, ckey_c, w_c, n


def run_starts(src_s, ckey_s):
    """Boolean mask marking the first edge of every (src, comm) run in a
    sorted slab."""
    first = jnp.ones((1,), dtype=bool)
    changed = (src_s[1:] != src_s[:-1]) | (ckey_s[1:] != ckey_s[:-1])
    return jnp.concatenate([first, changed])


def run_totals(w_s, starts):
    """Per-edge total weight of the (src, comm) run each edge belongs to.

    At run-start positions this is e_{i->c}, the aggregated weight from vertex
    i to community c — the value the reference stores in ``counter``
    (/root/reference/louvain.cpp:2419-2427).
    """
    ne_pad = w_s.shape[0]
    if ne_pad > SLAB_NE_MAX:
        raise ValueError(
            f"run_totals: slab has {ne_pad} rows, over SLAB_NE_MAX = "
            f"{SLAB_NE_MAX}: the int32 run-id cumsum would overflow")
    run_id = jnp.cumsum(starts.astype(jnp.int32)) - 1
    totals = segment_sum(w_s, run_id, num_segments=w_s.shape[0], sorted_ids=True)
    return jnp.take(totals, run_id), run_id
