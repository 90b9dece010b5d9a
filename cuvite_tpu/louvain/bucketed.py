"""Degree-bucketed Louvain step: the TPU analog of the reference GPU's
degree-class specialization.

The reference partitions vertices into three degree classes and runs a
different CUDA kernel per class (count_size_clmap,
/root/reference/louvain_cuda.cu:1426-1592; distGetMaxIndex variants
:878-1346; computeMaxIndex variants :230-876).  The equivalent TPU-first
move: bucket vertices by degree into FIXED-WIDTH padded rows
[n_bucket, D] whose edge gather indices are computed once per phase
(static shapes, one compile), and do the neighbor-community dedup +
gain + argmax as dense row-local ops that XLA fuses — no per-iteration
global sort, no hash maps.

Per row of width D the dedup is the O(D^2) all-pairs compare
(eq[j,k] = C[j]==C[k]); cheap for D <= ~64 and perfectly vectorized.
Vertices with degree > the largest bucket width go down the sort-based
path (cuvite_tpu/louvain/step.py machinery) restricted to THEIR edges
only — the analog of the reference's "huge" class using a different
algorithm entirely (dense scratch bincount, louvain_cuda.cu:878-1022).

Orchestration (what is static per phase vs dynamic per iteration):

  static per phase:  bucket membership, per-row dst/weight matrices,
                     per-vertex self-loop weight, heavy-edge subset
  per iteration:     one gather of comm[dst] per bucket, row-local
                     dedup/gain/argmax, community size/degree refresh
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from cuvite_tpu.ops import segment as seg

# Width ladder: ~1.5-2x steps bound the padded-slot inflation (a row of
# degree d occupies the next width up, so coarse factor-4 steps cost up to
# 4x the HBM traffic of the real edges — measured 1.75x faster step at
# scale-18 with this ladder vs (8,32,128,512,2048,8192)).  Every width
# >= 128 is a multiple of the TPU lane count so wide rows tile cleanly;
# the <=128 classes are lane-padded either way and stay cheap.
DEFAULT_BUCKETS = (8, 16, 32, 64, 128, 256, 384, 512, 768, 1024, 1536,
                   2048, 3072, 4096, 6144, 8192)


def _env_int(name: str, default: int) -> int:
    import os

    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        return int(raw)
    except ValueError:
        # A malformed knob (typo'd digit, stray unicode) must not silently
        # measure the baseline while the operator believes it changed.
        import warnings

        warnings.warn(
            f"{name}={raw!r} is not an integer; using default {default}",
            stacklevel=2)
        return default


# Dedup-kernel cutover (env-tunable for on-chip A/B): rows of width <=
# QUADRATIC_MAX_WIDTH dedup by the all-pairs compare (VPU/MXU-friendly
# O(D^2) with zero sorts/scans/gathers); wider rows take the packed
# per-row sort.  The crossover is hardware-dependent — the TPU vector
# units tolerate much larger D^2 than a scalar CPU does — so it is a
# load-time knob rather than a constant.
QUADRATIC_MAX_WIDTH = _env_int("CUVITE_QUAD_MAX", 32)
# Widest degree class routed through the Pallas row-argmax kernel by
# engine='pallas' (the XLA paths handle anything wider).  The kernel
# switches from an unrolled candidate loop to lax.fori_loop above
# kernels.row_argmax.UNROLL_MAX_WIDTH and shrinks its row tile to honor
# VMEM; 2048 keeps the [D, tile] blocks comfortably resident.  Knob for
# on-chip A/B runs.
PALLAS_MAX_WIDTH = _env_int("CUVITE_PALLAS_MAX", 2048)
ROW_CHUNK = _env_int("CUVITE_ROW_CHUNK", 8192)  # rows/lax.map step (quad)
# rows*width per lax.map step for the sorted dedup classes:
ROW_ELEMS_CHUNK = _env_int("CUVITE_ROW_ELEMS", 1 << 22)
# rows*width^2 bound for quad classes wider than the default 32 (the eq
# matrix is the transient that matters there):
ROW_QUAD_ELEMS_CHUNK = _env_int("CUVITE_QUAD_ELEMS", 1 << 26)


def chunk_for_width(width: int) -> int:
    """Rows per lax.map step — shared by the plan builder (row padding) and
    the step (chunk dispatch); a mismatch would silently disable chunking.
    Rounded DOWN to a power of two: row counts are pow2-padded, and pow2
    rows divide evenly only by pow2 chunks (a non-pow2 chunk — e.g. from
    the 384/768/... widths — would make every large bucket fall back to
    the unchunked path and blow the transient-memory bound)."""
    def pow2_floor(c: int) -> int:
        c = max(c, 1)
        return 1 << (c.bit_length() - 1)

    if width <= QUADRATIC_MAX_WIDTH:
        # Quad classes: the [chunk, D, D] eq matrix is the transient that
        # matters — bound rows*D^2, capped by the fixed row-count knob.
        # (For the default widths <= 32 the row-count cap always wins, so
        # this reproduces the historical ROW_CHUNK=8192 chunks exactly.)
        return min(pow2_floor(ROW_CHUNK),
                   pow2_floor(ROW_QUAD_ELEMS_CHUNK // (width * width)))
    return pow2_floor(ROW_ELEMS_CHUNK // width)


@dataclasses.dataclass
class Bucket:
    width: int
    verts: np.ndarray    # [Nb] local vertex indices
    dst: np.ndarray      # [Nb, D] GLOBAL (padded-space) tail ids; pad -> self
    w: np.ndarray        # [Nb, D] weights; pad -> 0


@dataclasses.dataclass
class BucketPlan:
    """Phase-static layout for one shard's edge slab."""

    nv_local: int
    buckets: list            # list[Bucket]
    heavy_src: np.ndarray    # [NEh_pad] local src idx of heavy edges (pad nv)
    heavy_dst: np.ndarray    # [NEh_pad] global tail ids (pad 0)
    heavy_w: np.ndarray      # [NEh_pad] weights (pad 0)
    self_loop: np.ndarray    # [nv_local] per-vertex self-loop weight
    has_heavy: bool

    @staticmethod
    def build(
        src: np.ndarray,
        dst: np.ndarray,
        w: np.ndarray,
        nv_local: int,
        base: int,
        widths: tuple = DEFAULT_BUCKETS,
    ) -> "BucketPlan":
        """`src` holds local indices (pad = nv_local); `dst` global padded
        ids; `base` is this shard's first global id (for self-loop
        detection)."""
        plan = _build_native(src, dst, w, nv_local, base, widths)
        if plan is not None:
            return plan
        real = src < nv_local
        s = src[real].astype(np.int64)
        d = dst[real].astype(np.int64)
        ww = w[real].astype(np.float64)
        deg = np.bincount(s, minlength=nv_local)
        # Slabs cut from a CSR arrive row-ordered (DistGraph.build expands
        # offsets in vertex order), so the O(ne log ne) stable sort is
        # usually a no-op — skip it after an O(ne) check.  Color-class
        # plans mask rows to nv_local and DO need the sort.
        if len(s) and np.any(s[:-1] > s[1:]):
            order = np.argsort(s, kind="stable")
            s, d, ww = s[order], d[order], ww[order]
        row_start = np.concatenate([[0], np.cumsum(deg)[:-1]]).astype(np.int64)

        self_loop = np.zeros(nv_local, dtype=np.float64)
        is_self = d == (s + base)
        np.add.at(self_loop, s[is_self], ww[is_self])

        # Unit-weight graphs (R-MAT, unweighted inputs): every real edge
        # weighs exactly 1, so the per-bucket weight matrix IS the has-edge
        # mask — skip the [nb, width] f64 weight gather entirely and emit
        # uint8 (the dtype the device upload wants anyway, see
        # compress_unit_weights).  Deliberately NARROWER than
        # is_unit_weights: that predicate admits {0, 1} mixtures (safe for
        # dtype compression of an already-built matrix), but the mask
        # substitution here requires every real edge to weigh exactly 1 —
        # a real 0-weight edge would be promoted to 1 by the mask.
        unit = len(ww) == 0 or bool(np.all(ww == 1.0))

        buckets = []
        prev = 0
        for width in widths:
            sel = np.nonzero((deg > prev) & (deg <= width))[0]
            prev = width
            if len(sel) == 0:
                continue
            nb = len(sel)
            # Pad the row count to the next power of two: stable shapes let
            # successive coarsened phases reuse the compiled step (pow2 >
            # chunk is automatically a multiple of the pow2 chunk, so
            # lax.map chunking stays exact).  Padding rows use local index
            # nv_local (dropped by out-of-bounds scatter).
            nb_pad = 1 << int(nb - 1).bit_length() if nb > 1 else 1
            verts = np.full(nb_pad, nv_local, dtype=np.int64)
            verts[:nb] = sel
            dmat = np.zeros((nb_pad, width), dtype=dst.dtype)
            # One vectorized gather per bucket; column padding uses the
            # vertex's own global id with weight 0 (a zero-weight self-edge
            # never becomes a candidate and adds 0 to counter0).
            cols = np.arange(width)
            idx = row_start[sel][:, None] + cols[None, :]
            has = cols[None, :] < deg[sel][:, None]
            idx = np.minimum(idx, max(len(d) - 1, 0))
            dmat[:nb] = np.where(has, d[idx], (sel + base)[:, None])
            if unit:
                wmat = np.zeros((nb_pad, width), dtype=np.uint8)
                wmat[:nb] = has
            else:
                wmat = np.zeros((nb_pad, width), dtype=w.dtype)
                wmat[:nb] = np.where(has, ww[idx], 0.0)
            buckets.append(Bucket(width=width, verts=verts, dst=dmat, w=wmat))

        heavy_v = np.nonzero(deg > widths[-1])[0]
        if len(heavy_v):
            # Boolean-table lookup instead of np.isin: O(ne) vs isin's
            # sort-based O(ne log ne) (~0.1 s/phase at scale 18).
            is_heavy = np.zeros(nv_local + 1, dtype=bool)
            is_heavy[heavy_v] = True
            hmask = is_heavy[s]
            hs, hd, hw = s[hmask], d[hmask], ww[hmask]
            n = len(hs)
            npad = max(int(2 ** np.ceil(np.log2(max(n, 1)))), 8)
            heavy_src = np.full(npad, nv_local, dtype=src.dtype)
            heavy_dst = np.zeros(npad, dtype=dst.dtype)
            heavy_w = np.zeros(npad, dtype=w.dtype)
            heavy_src[:n] = hs
            heavy_dst[:n] = hd
            heavy_w[:n] = hw
            has_heavy = True
        else:
            heavy_src = np.full(8, nv_local, dtype=src.dtype)
            heavy_dst = np.zeros(8, dtype=dst.dtype)
            heavy_w = np.zeros(8, dtype=w.dtype)
            has_heavy = False
        return BucketPlan(
            nv_local=nv_local,
            buckets=buckets,
            heavy_src=heavy_src,
            heavy_dst=heavy_dst,
            heavy_w=heavy_w,
            self_loop=self_loop.astype(w.dtype),
            has_heavy=has_heavy,
        )


def _build_native(src, dst, w, nv_local, base, widths):
    """Native-streamed BucketPlan (cv_plan_scan + cv_bucket_fill): two O(E)
    C++ passes with no transient larger than O(nv), vs the numpy path's
    multi-gigabyte int64 copies and per-class gather matrices at benchmark
    scales (VERDICT r2 item 3).  Returns None — caller falls back to numpy
    — when the library is unavailable, the slab is small, dtypes are mixed,
    or the slab is not CSR-sorted with tail padding (e.g. the color-class
    masked plans).  Output is bit-identical to the numpy path (pinned by
    tests/test_native.py)."""
    from cuvite_tpu import native as cvn

    if (not cvn.available() or len(src) < cvn.MIN_NATIVE_EDGES
            or src.dtype != dst.dtype
            or src.dtype not in (np.int32, np.int64)
            or w.dtype not in (np.float32, np.float64)
            or not (src.flags.c_contiguous and dst.flags.c_contiguous
                    and w.flags.c_contiguous)):
        return None
    self_loop64, sorted_, unit, tail_ok = cvn.plan_scan(
        src, dst, w, nv_local, base)
    if not (sorted_ and tail_ok):
        return None
    deg = np.bincount(src, minlength=nv_local + 1)[:nv_local]
    widths_arr = np.asarray(widths, dtype=np.int64)
    nw = len(widths_arr)
    cls_idx = np.searchsorted(widths_arr, deg, side="left")
    heavy_mask = deg > widths_arr[-1]
    in_bucket = (deg > 0) & ~heavy_mask
    full_counts = np.bincount(cls_idx[in_bucket], minlength=nw)
    kept = np.nonzero(full_counts)[0]
    remap = np.full(nw + 1, 255, dtype=np.uint8)
    remap[kept] = np.arange(len(kept), dtype=np.uint8)
    cls = np.full(nv_local, 255, dtype=np.uint8)
    cls[in_bucket] = remap[cls_idx[in_bucket]]
    cls[heavy_mask] = 254
    row_start = np.zeros(nv_local, dtype=np.int64)
    np.cumsum(deg[:-1], out=row_start[1:])

    nb = full_counts[kept]
    nb_pad = np.array(
        [1 << int(n - 1).bit_length() if n > 1 else 1 for n in nb],
        dtype=np.int64)
    widths_kept = widths_arr[kept]
    wm_dtype = np.uint8 if unit else w.dtype
    # O(E) plan arrays are allocated 64-byte aligned so the cpu-backend
    # upload aliases them instead of duplicating (utils/upload.py).
    from cuvite_tpu.utils.upload import aligned_full, aligned_zeros

    verts_list, dmat_list, wmat_list = [], [], []
    for np_, width in zip(nb_pad, widths_kept):
        verts_list.append(aligned_full(np_, nv_local, np.int64))
        dmat_list.append(aligned_zeros((np_, width), dst.dtype))
        wmat_list.append(aligned_zeros((np_, width), wm_dtype))
    n_h = int(deg[heavy_mask].sum())
    if n_h:
        heavy_pad = max(int(2 ** np.ceil(np.log2(max(n_h, 1)))), 8)
    else:
        heavy_pad = 8
    heavy_src = aligned_full(heavy_pad, nv_local, src.dtype)
    heavy_dst = aligned_zeros(heavy_pad, dst.dtype)
    heavy_w = aligned_zeros(heavy_pad, w.dtype)
    cvn.bucket_fill(dst, w, nv_local, base, row_start,
                    deg.astype(np.int64), cls, widths_kept, nb_pad,
                    verts_list, dmat_list, wmat_list, unit, heavy_pad,
                    heavy_src, heavy_dst, heavy_w)
    buckets = [
        Bucket(width=int(width), verts=v, dst=d, w=ww)
        for width, v, d, ww in zip(widths_kept, verts_list, dmat_list,
                                   wmat_list)
    ]
    return BucketPlan(
        nv_local=nv_local,
        buckets=buckets,
        heavy_src=heavy_src,
        heavy_dst=heavy_dst,
        heavy_w=heavy_w,
        self_loop=self_loop64.astype(w.dtype),
        has_heavy=n_h > 0,
    )


@dataclasses.dataclass
class StackedPlan:
    """Per-shard BucketPlans padded to COMMON shapes and stacked shard-major,
    ready to be sharded along axis 0 of a 1-D mesh (every shard must present
    identical bucket geometry to the SPMD step — the analog of the
    reference's per-rank symmetric kernel launches)."""

    buckets: list            # list of (verts [S*Nb], dst [S*Nb, D], w [S*Nb, D])
    heavy: tuple             # (src [S*H], dst [S*H], w [S*H])
    self_loop: np.ndarray    # [S*nv_pad]
    perm: np.ndarray         # [S*nv_pad] per-shard assembly permutation
    unit_weights: np.ndarray  # [n_buckets] bool: w is {0,1} on EVERY host
    # Kernel routing (engine='pallas' on a mesh): per kept bucket, True if
    # its width class is laid out for the Pallas row kernel (row count
    # padded to >= LANE so the per-shard [D, Nb] block tiles cleanly).
    pallas_flags: tuple = ()
    # Per-width real (directed) edge counts, [len(widths) + 1] with the
    # trailing slot the heavy residual — allreduced across hosts under
    # per-host ingest.  Only populated when ``pallas_widths`` was given
    # (coverage accounting costs one O(E) bincount per shard).
    width_edges: np.ndarray | None = None


def build_stacked_plans(dg, widths: tuple = DEFAULT_BUCKETS,
                        exchange_plan=None, class_of=None,
                        class_id: int = -1,
                        pallas_widths: tuple = (),
                        count_width_edges: bool = False) -> StackedPlan:
    """Build one BucketPlan per shard of ``dg`` and pad them to common
    shapes.  A width class appears iff some shard has vertices in it; shards
    without rows in a kept class contribute all-padding rows.

    With ``exchange_plan`` (a comm.exchange.ExchangePlan) dst ids are
    remapped into each shard's extended-local space [0, nv_pad + ghost_pad)
    — the layout the sparse-exchange step gathers from — and self-loop
    detection switches to the local formulation (base=0: remapped self edge
    has dst == src local index).

    Per-host-ingest partitions (``dg.local_only``, io/dist_ingest.py) build
    plans for THIS process's shard rows only; the padded shapes (which must
    be identical on every process for one SPMD program) are agreed by a
    host max-allreduce, and the returned arrays' leading dim covers local
    shards only — place them with comm.multihost.place_block.

    ``class_of`` (padded GLOBAL id space, [S*nv_pad]) with ``class_id``
    restricts each shard's plan to the vertices of one color class (other
    rows masked to padding) — the SPMD analog of the single-shard
    class-restricted plans (the reference sweeps only the class's vertices
    on every rank, /root/reference/louvain.cpp:862-901).

    ``pallas_widths`` (engine='pallas' on a mesh): width classes to lay
    out for the Pallas row kernel — their COMMON row counts are padded up
    to >= 128 (the kernel's lane tile; counts are pow2 already, so this
    only lifts the sub-128 classes) and flagged in ``pallas_flags``; the
    runner transposes those classes to [S*D, Nb] at placement.  Also
    triggers the per-width edge accounting (``width_edges``) behind the
    engine's kernel-coverage report; ``count_width_edges`` forces that
    accounting even when no width qualifies (a CUVITE_PALLAS_MAX tuned
    below the smallest bucket width must still report ITS coverage: 0)."""
    nshards = dg.nshards
    nvl = dg.nv_pad
    local_only = getattr(dg, "local_only", False)
    lo, hi = (dg.local_lo, dg.local_hi) if local_only else (0, nshards)
    sids = range(lo, hi)

    def _mask_src(s):
        src = np.asarray(dg.shards[s].src)
        if class_of is None:
            return src
        cls_local = np.asarray(class_of)[s * nvl:(s + 1) * nvl]
        in_cls = cls_local[np.minimum(src, nvl - 1)] == class_id
        return np.where((src < nvl) & in_cls, src, nvl).astype(src.dtype)

    if exchange_plan is not None:
        # Class-restricted sparse plans (reference's distributed -c/-d,
        # /root/reference/louvain.cpp:862-901): the ghost ROUTING is
        # class-independent — every class plan shares the phase's
        # send_idx/ghost_sel and extended-local dst space — only the row
        # masking differs.  remap_dst sees the MASKED src, so masked-out
        # edges map to dst 0 and are dropped as padding.
        # Grouped (two-level) plans remap dst into GROUP-local space, so
        # shard s's self edge lands at (s % ici)*nvl + src, not src: the
        # base is the shard's offset within its dcn group (0 for flat
        # plans, where ici == 1 and the two formulations coincide).
        grp_ici = getattr(exchange_plan, "ici", 1) or 1

        def _sparse_plan(s):
            ms = _mask_src(s)   # one O(E) masking pass, shared
            return BucketPlan.build(
                ms,
                exchange_plan.remap_dst(
                    s, ms, np.asarray(dg.shards[s].dst)
                ).astype(np.asarray(dg.shards[s].dst).dtype),
                np.asarray(dg.shards[s].w),
                nv_local=nvl, base=(s % grp_ici) * nvl, widths=widths,
            )

        plans = [_sparse_plan(s) for s in sids]
    else:
        plans = [
            BucketPlan.build(
                _mask_src(s), np.asarray(dg.shards[s].dst),
                np.asarray(dg.shards[s].w),
                nv_local=nvl, base=s * nvl, widths=widths,
            )
            for s in sids
        ]
    n_rows = len(plans)
    by_width = [{b.width: b for b in p.buckets} for p in plans]
    shape_req = np.array(
        [max((len(bw[w].verts) for bw in by_width if w in bw), default=0)
         for w in widths]
        + [max(len(p.heavy_src) for p in plans)], dtype=np.int64)
    if local_only:
        from cuvite_tpu.comm.multihost import allreduce_max_host

        shape_req = allreduce_max_host(shape_req)
    width_edges = None
    if pallas_widths or count_width_edges:
        # Kernel-coverage accounting: real directed edges per width class
        # (+ heavy residual).  One O(E) bincount per local shard, summed
        # across hosts — deterministic, so every process reports the same
        # coverage.
        widths_arr = np.asarray(widths, dtype=np.int64)
        width_edges = np.zeros(len(widths) + 1, dtype=np.int64)
        for s in sids:
            ms = _mask_src(s)
            deg = np.bincount(ms[ms < nvl], minlength=nvl)
            heavy_m = deg > widths_arr[-1]
            in_b = (deg > 0) & ~heavy_m
            cls = np.searchsorted(widths_arr, deg[in_b], side="left")
            width_edges[: len(widths)] += np.bincount(
                cls, weights=deg[in_b], minlength=len(widths)
            ).astype(np.int64)
            width_edges[-1] += int(deg[heavy_m].sum())
        if local_only:
            from cuvite_tpu.comm.multihost import allreduce_sum_host

            width_edges = np.asarray(allreduce_sum_host(width_edges))
    stacked_buckets = []
    pallas_flags = []
    for wi, width in enumerate(widths):
        nb = int(shape_req[wi])
        if nb == 0:
            continue
        if width in pallas_widths:
            # The kernel's row dimension must be a multiple of its 128-lane
            # tile; counts are pow2 (see BucketPlan.build), so only the
            # sub-128 classes grow.  max keeps every process's agreed
            # shape_req deterministic.
            nb = max(nb, 128)
        pallas_flags.append(width in pallas_widths)
        verts = np.full((n_rows, nb), nvl, dtype=np.int64)
        dmat = np.zeros((n_rows, nb, width), dtype=plans[0].heavy_dst.dtype)
        wmat = np.zeros((n_rows, nb, width), dtype=plans[0].heavy_w.dtype)
        for r, bw in enumerate(by_width):
            if width in bw:
                b = bw[width]
                verts[r, : len(b.verts)] = b.verts
                dmat[r, : len(b.verts)] = b.dst
                wmat[r, : len(b.verts)] = b.w
        stacked_buckets.append(
            (verts.reshape(-1), dmat.reshape(-1, width),
             wmat.reshape(-1, width))
        )
    hn = int(shape_req[-1])
    hsrc = np.full((n_rows, hn), nvl, dtype=plans[0].heavy_src.dtype)
    hdst = np.zeros((n_rows, hn), dtype=plans[0].heavy_dst.dtype)
    hw = np.zeros((n_rows, hn), dtype=plans[0].heavy_w.dtype)
    for r, p in enumerate(plans):
        hsrc[r, : len(p.heavy_src)] = p.heavy_src
        hdst[r, : len(p.heavy_dst)] = p.heavy_dst
        hw[r, : len(p.heavy_w)] = p.heavy_w
    self_loop = np.concatenate([p.self_loop for p in plans])
    # Per-shard assembly permutation over the COMMON padded layout (every
    # shard's concat space has identical extent, so one [nv_pad] perm per
    # shard row, stacked like the other plan arrays).
    perm = np.stack([
        build_assemble_perm([sb[0].reshape(n_rows, -1)[r]
                             for sb in stacked_buckets], nvl)
        for r in range(n_rows)
    ]) if n_rows else np.zeros((0, nvl), dtype=np.int32)
    # Per-bucket unit-weight flags (uint8 upload eligibility) must agree on
    # every process under per-host ingest — a weighted shard on one host
    # and an all-padding block on another would otherwise build the same
    # global array with different dtypes.  Min-allreduce the local verdicts
    # (min == negated max).
    unit = np.array([is_unit_weights(sb[2]) for sb in stacked_buckets],
                    dtype=np.int64)
    if local_only:
        from cuvite_tpu.comm.multihost import allreduce_max_host

        unit = -allreduce_max_host(-unit)
    return StackedPlan(
        buckets=stacked_buckets,
        heavy=(hsrc.reshape(-1), hdst.reshape(-1), hw.reshape(-1)),
        self_loop=self_loop,
        perm=perm.reshape(-1),
        unit_weights=unit.astype(bool),
        pallas_flags=tuple(pallas_flags),
        width_edges=width_edges,
    )


def is_unit_weights(w: np.ndarray) -> bool:
    """True when every entry is exactly 0 or 1 — the uint8 DTYPE-compression
    eligibility rule for already-built weight matrices (single-shard and
    stacked upload paths).  Distinct from BucketPlan.build's stricter
    mask-substitution predicate (all real weights exactly 1), which must
    reject {0, 1} mixtures."""
    return bool(w.size) and bool(np.all((w == 0) | (w == 1)))


def compress_unit_weights(w: np.ndarray, wdt) -> np.ndarray:
    """Return ``w`` as uint8 when :func:`is_unit_weights`, else as ``wdt``.

    uint8 bucket weights cost 4x less host->device upload and 4x less HBM
    read per iteration; the step casts back to the weight dtype on use
    (fused by XLA), and 0/1 cast exactly, so results are bit-identical."""
    if is_unit_weights(w):
        return w.astype(np.uint8)
    return w.astype(wdt)


def build_assemble_perm(verts_list, nv_local: int) -> np.ndarray:
    """Vertex -> position in the concatenated bucket-row space.

    ``verts_list``: the PADDED per-bucket vertex arrays exactly as uploaded
    (padding entries hold >= nv_local and are skipped).  Vertices in no
    bucket (heavy / degree-0) map to the trailing default slot.  Bucket
    membership is disjoint, so the map is a pure (partial) permutation —
    this is what lets the step assemble results with gathers instead of
    scatters."""
    total = sum(len(v) for v in verts_list)
    perm = np.full(nv_local, total, dtype=np.int32)
    off = 0
    for v in verts_list:
        v = np.asarray(v)
        real = np.nonzero(v < nv_local)[0]
        perm[v[real]] = (off + real).astype(np.int32)
        off += len(v)
    return perm


class RowResult(NamedTuple):
    best_c: jax.Array    # [Nb] best candidate community (sentinel if none)
    best_gain: jax.Array  # [Nb]
    counter0: jax.Array  # [Nb] weight to current community (incl self-loops)
    best_size: jax.Array | None  # [Nb] size of best community (sparse mode)


def _row_argmax(cmat, wmat, aymat, smat, curr_comm, vdeg_v, sl_v, ax_v,
                constant, sentinel):
    """Dedup + dQ + argmax for one chunk of bucket rows.

    cmat [T, D] neighbor communities; wmat [T, D] weights; aymat [T, D] the
    candidate community's degree a_y per slot; smat [T, D] (or None) the
    candidate community's size per slot; sl_v [T] the vertex's self-loop
    weight (e_ix = counter0 - sl is row-local: every edge of a bucket
    vertex lives in its row); ax_v [T] = a_x = deg(curr) - k_i.
    Replicates distGetMaxIndex (/root/reference/louvain.cpp:2185-2244):
    gain = 2*(e_iy - e_ix) - 2*k_i*(a_y - a_x)/2m, ties to smaller id.
    """
    wdt = wmat.dtype
    # all-pairs equality within the row: eq[t, j, k] = C[j] == C[k]
    eq = cmat[:, :, None] == cmat[:, None, :]
    # aggregated weight per slot: sum over duplicates
    wagg = jnp.einsum("tjk,tk->tj", eq.astype(wdt), wmat)
    # leader slot = first occurrence of its community
    tri = jnp.tril(jnp.ones((cmat.shape[1], cmat.shape[1]), dtype=bool), k=-1)
    dup = jnp.any(eq & tri[None, :, :], axis=2)
    is_cc = cmat == curr_comm[:, None]
    counter0 = jnp.sum(jnp.where(is_cc, wmat, 0.0), axis=1)
    eix_v = counter0 - sl_v
    # No w>0 filter: zero-weight edges are candidates exactly as in the sort
    # engine.  Padding slots are safe without it — they point at the row's
    # own vertex, whose community always equals curr_comm, so is_cc masks
    # them out of the candidate set.
    valid = (~dup) & (~is_cc)

    gain = 2.0 * (wagg - eix_v[:, None]) \
        - 2.0 * vdeg_v[:, None] * (aymat - ax_v[:, None]) * constant
    neg_inf = jnp.array(-jnp.inf, dtype=wdt)
    gain = jnp.where(valid, gain, neg_inf)
    best_gain = jnp.max(gain, axis=1)
    at_best = valid & (gain == best_gain[:, None])
    best_c = jnp.min(
        jnp.where(at_best, cmat, jnp.full_like(cmat, sentinel)), axis=1
    )
    best_size = None
    if smat is not None:
        # size of the winning community: any slot with that community id
        # carries the same attached size.
        chosen = cmat == best_c[:, None]
        best_size = jnp.min(
            jnp.where(chosen, smat, jnp.full_like(smat, sentinel)), axis=1
        )
    return RowResult(best_c=best_c, best_gain=best_gain, counter0=counter0,
                     best_size=best_size)


def _row_argmax_sorted(cmat, wmat, aymat, smat, curr_comm, vdeg_v, sl_v,
                       ax_v, constant, sentinel, id_bound=None):
    """Dedup + dQ + argmax for wide rows via a per-row sort.

    O(D log^2 D) per row instead of the all-pairs O(D^2): sort each row by
    community id, detect runs, and compute run sums with a reverse cumsum +
    next-leader index (reverse cummin) — all lane-parallel scans.  This is
    the TPU counterpart of the reference's medium/large GPU kernels
    (/root/reference/louvain_cuda.cu:1024-1346).

    When every community id provably fits in ``31 - bits(D)`` bits
    (``id_bound``, static), the sort runs on ONE packed int32 key
    ``(c << bits) | slot`` and the payloads follow by take_along_axis —
    measured 4-5x faster than the multi-operand comparator sort, with
    bit-identical results (packed keys are unique, so the stable order by
    (c, slot) equals the stable order by c).
    """
    wdt = wmat.dtype
    D = cmat.shape[1]
    # counter0 in UNSORTED slot order (the historical outer-pass order, so
    # modularity and e_ix stay bit-identical to the two-pass formulation).
    counter0 = jnp.sum(
        jnp.where(cmat == curr_comm[:, None], wmat, 0.0), axis=1
    ).astype(wdt)
    eix_v = counter0 - sl_v
    bits = (D - 1).bit_length()
    packable = (
        id_bound is not None
        and cmat.dtype == jnp.int32
        and (int(id_bound) << bits) <= (1 << 31)
    )
    if packable:
        iota = jax.lax.broadcasted_iota(jnp.int32, cmat.shape, 1)
        k_s = jax.lax.sort((cmat << bits) | iota, dimension=1)
        slot = k_s & ((1 << bits) - 1)
        c_s = k_s >> bits
        w_s = jnp.take_along_axis(wmat, slot, axis=1)
        ay_s = jnp.take_along_axis(aymat, slot, axis=1)
        s_s = (jnp.take_along_axis(smat, slot, axis=1)
               if smat is not None else None)
    elif smat is not None:
        c_s, w_s, ay_s, s_s = jax.lax.sort(
            (cmat, wmat, aymat, smat), dimension=1, num_keys=1)
    else:
        c_s, w_s, ay_s = jax.lax.sort(
            (cmat, wmat, aymat), dimension=1, num_keys=1)
    leader = jnp.concatenate(
        [jnp.ones_like(c_s[:, :1], dtype=bool), c_s[:, 1:] != c_s[:, :-1]],
        axis=1,
    )
    pos = jax.lax.broadcasted_iota(jnp.int32, c_s.shape, 1)
    leaderpos = jnp.where(leader, pos, D)
    # next leader strictly to the right of j (D if none)
    nxt = jnp.flip(jax.lax.cummin(jnp.flip(leaderpos, 1), axis=1), 1)
    nxt = jnp.concatenate(
        [nxt[:, 1:], jnp.full_like(nxt[:, :1], D)], axis=1
    )
    # suffix sums S[j] = sum_{k >= j} w; S_ext has trailing 0 column
    suf = jnp.flip(jnp.cumsum(jnp.flip(w_s, 1), axis=1), 1)
    suf_ext = jnp.concatenate([suf, jnp.zeros_like(suf[:, :1])], axis=1)
    run_sum = suf - jnp.take_along_axis(suf_ext, nxt, axis=1)

    is_cc = c_s == curr_comm[:, None]
    # No w>0 filter — see _row_argmax; padding self-slots are is_cc-masked.
    valid = leader & (~is_cc)

    gain = 2.0 * (run_sum - eix_v[:, None]) \
        - 2.0 * vdeg_v[:, None] * (ay_s - ax_v[:, None]) * constant
    neg_inf = jnp.array(-jnp.inf, dtype=wdt)
    gain = jnp.where(valid, gain, neg_inf)
    best_gain = jnp.max(gain, axis=1)
    at_best = valid & (gain == best_gain[:, None])
    best_c = jnp.min(
        jnp.where(at_best, c_s, jnp.full_like(c_s, sentinel)), axis=1
    )
    best_size = None
    if smat is not None:
        chosen = c_s == best_c[:, None]
        best_size = jnp.min(
            jnp.where(chosen, s_s, jnp.full_like(s_s, sentinel)), axis=1
        )
    return RowResult(best_c=best_c, best_gain=best_gain, counter0=counter0,
                     best_size=best_size)


def _map_chunks(fn, nb, chunk, row_arrays):
    """Shared chunk dispatch: run ``fn`` over [chunk]-row slices of
    ``row_arrays`` via lax.map, or in one piece when ``nb`` doesn't divide
    (row counts are pow2-padded and ``chunk_for_width`` returns pow2, so
    the divisibility check only fails for sub-chunk buckets).  Returns the
    lax.map-stacked pytree — callers reshape leading dims back to [nb].
    One definition so the dispatch rule cannot drift between the argmax
    pass and the modularity c0 pass."""
    if nb <= chunk or nb % chunk != 0:
        return fn(*row_arrays)
    nchunk = nb // chunk
    return jax.lax.map(
        lambda args: fn(*args),
        tuple(a.reshape((nchunk, chunk) + a.shape[1:]) for a in row_arrays),
    )


def _rows_chunked(w_mat, dst_mat, curr, vdeg_v, sl_v, ax_v,
                  constant, sentinel, gather_cm, gather_ay, gather_sz,
                  wdt, id_bound=None):
    """Dispatch rows to the right dedup variant, chunked with lax.map to
    bound intermediate memory.  Every O(rows x D) operand that is not a
    phase-static plan constant is produced INSIDE the chunk body:
    ``gather_cm`` maps a dst chunk to its community matrix, ``gather_ay``/
    ``gather_sz`` produce the per-slot community degree / size matrices,
    and uint8-compressed unit weights widen to ``wdt`` per chunk.  XLA
    cannot fuse producers into a lax.map (scan) body, so a full-bucket
    cmat gather or weight cast at the caller would materialize the whole
    O(E) matrix — at benchmark scale, tens of GB of step-resident
    buffers (the scale-26 attempt-1 OOM, tools/scale26_attempts.md).
    ``gather_sz`` may return None in replicated mode."""
    nb, width = dst_mat.shape
    kernel = (_row_argmax if width <= QUADRATIC_MAX_WIDTH
              else functools.partial(_row_argmax_sorted, id_bound=id_bound))

    def run(wm, dm, cu, vd, sl, ax):
        if wm.dtype != wdt:  # uint8-compressed unit weights
            wm = wm.astype(wdt)
        cm = gather_cm(dm)
        return kernel(cm, wm, gather_ay(dm, cm), gather_sz(dm, cm),
                      cu, vd, sl, ax, constant, sentinel)

    res = _map_chunks(run, nb, chunk_for_width(width),
                      (w_mat, dst_mat, curr, vdeg_v, sl_v, ax_v))
    return RowResult(
        best_c=res.best_c.reshape(nb),
        best_gain=res.best_gain.reshape(nb),
        counter0=res.counter0.reshape(nb),
        best_size=(None if res.best_size is None
                   else res.best_size.reshape(nb)),
    )


def bucketed_modularity(bucket_arrays, heavy_arrays, self_loop, comm, vdeg,
                        constant, *, nv_total, accum_dtype=None,
                        axis_name=None, sparse_plan=None, nshards=1,
                        budget=0, ici_axis=None):
    """Modularity of ``comm`` alone (no argmax): one cheap masked-sum pass
    over the bucket rows + heavy slab.  Used by the color-scheduled
    iteration, whose per-class steps see partial states — this gives the
    iteration's Q at its START state for the convergence check at ~the cost
    of the counter0 pass.  With ``axis_name`` it runs SPMD inside shard_map
    (replicated exchange: all_gather'ed community vector, psum'd terms).

    With ``sparse_plan`` the pass rides the sparse ghost exchange instead
    (dst ids extended-local, owner-sharded a² term) and RETURNS
    ``(modularity, overflow)`` — the budgeted owner-reduce behind the a²
    term can overflow exactly like the step's.  ``ici_axis`` upgrades the
    sparse exchange to the two-level scheme: ``axis_name`` is then the
    slow DCN axis, the plan a grouped one, and the per-edge terms reduce
    over BOTH axes while the a² term stays on the DCN axis only (the
    group tables are ICI-replicated)."""
    nv_local = comm.shape[0]
    wdt = vdeg.dtype
    use_sparse = sparse_plan is not None
    red_axes = (axis_name if ici_axis is None else (axis_name, ici_axis))
    if use_sparse:
        from cuvite_tpu.comm.exchange import (
            sparse_env, sparse_modularity, twolevel_env)

        assert axis_name is not None, "sparse exchange requires a mesh axis"
        if ici_axis is not None:
            env = twolevel_env(comm, vdeg, sparse_plan[0], sparse_plan[1],
                               axis_name, ici_axis, n_dcn=nshards,
                               budget=budget)
        else:
            env = sparse_env(comm, vdeg, sparse_plan[0], sparse_plan[1],
                             axis_name, nshards=nshards, budget=budget)
        comm_full = env.comm_ext
    else:
        comm_full, gsum = seg.spmd_env(comm, axis_name)
        comm_deg = gsum(seg.segment_sum(vdeg, comm, num_segments=nv_total))  # graftlint: replicated-ok=scope=ici; replicated-exchange mod pass, flat-mesh-only (hybrid meshes take the sparse/two-level branch above)
    counter0 = jnp.zeros((nv_local,), dtype=wdt)
    hs, hd, hw = heavy_arrays
    ckey_h = jnp.take(comm_full, hd)
    csrc_h = jnp.take(comm, jnp.minimum(hs, nv_local - 1))
    counter0 = counter0 + seg.segment_sum(
        jnp.where(ckey_h == csrc_h, hw, jnp.zeros_like(hw)), hs,
        num_segments=nv_local,
    )
    for verts, dst_mat, w_mat in bucket_arrays:
        safe_v = jnp.minimum(verts, nv_local - 1)
        curr = jnp.take(comm, safe_v)

        def c0_of(wm, dm, cu):
            # Gather + uint8 widening INSIDE the chunk (same reasoning as
            # _rows_chunked: producers can't fuse into a lax.map body, so
            # doing this at full bucket size materializes O(E) buffers).
            if wm.dtype != wdt:
                wm = wm.astype(wdt)
            cm = jnp.take(comm_full, dm)
            return jnp.sum(
                jnp.where(cm == cu[:, None], wm, 0.0), axis=1
            ).astype(wdt)

        nb, width = dst_mat.shape
        c0_rows = _map_chunks(c0_of, nb, chunk_for_width(width),
                              (w_mat, dst_mat, curr)).reshape(nb)
        counter0 = counter0.at[verts].add(c0_rows, mode="drop")
    if use_sparse:
        mod = sparse_modularity(counter0, env.deg_local, constant,
                                red_axes, accum_dtype,
                                deg_axis_name=axis_name)
        overflow = jax.lax.psum(env.overflow.astype(jnp.int32),
                                red_axes) > 0
        return mod, overflow
    return seg.modularity_terms(counter0, comm_deg, constant,
                                gsum, accum_dtype, axis_name=axis_name)


def bucketed_step(bucket_arrays, heavy_arrays, self_loop, comm, vdeg,
                  constant, *, nv_total, sentinel, accum_dtype=None,
                  axis_name=None, pallas_flags=(), pallas_interpret=False,
                  sparse_plan=None, nshards=1, budget=0, ici_axis=None,
                  info_comm=None, assemble_perm=None, heavy_kernel=None):
    """Full Louvain sweep over one shard using the bucketed engine.

    ``assemble_perm`` (phase-static [nv_local] int32, vertex -> index into
    the bucket-row concat space, trailing index = "in no bucket"): enables
    the scatter-free assembly of per-vertex results — TPU scatters are
    serialization hazards; a static permutation gather is not.  Semantics
    are identical with or without it.

    ``bucket_arrays`` is a tuple of (verts, dst_mat, w_mat) triples (one per
    degree class); ``heavy_arrays`` is (src, dst, w) for the residual
    heavy-vertex edges (may be empty-padded).  Returns (target, modularity,
    n_moved, overflow) with step semantics identical to louvain_step_local —
    the two engines are interchangeable and tested for equal outputs.
    ``overflow`` is the sparse-exchange budget flag (constant False under
    the replicated exchange).

    ``pallas_flags`` (one bool per bucket) routes flagged degree classes
    through the Pallas row-argmax kernel (cuvite_tpu/kernels/row_argmax.py);
    those buckets' dst/w matrices must be stored TRANSPOSED [D, Nb] with Nb
    a multiple of 128 (the runner's ``engine='pallas'`` upload does this,
    single-shard and SPMD alike — on a mesh the kernel runs INSIDE the
    shard_map body on each shard's block, under either exchange: the
    replicated mode feeds it the psum'd community-degree table, the sparse
    mode the vertex-attached cdeg/csize extended-local tables, with the
    winning community's size tracked in-kernel for the singleton guard).

    With ``axis_name`` the function runs SPMD inside shard_map: ``comm`` /
    ``vdeg`` / ``self_loop`` are this shard's slices.  Two exchange modes
    implement the cross-shard community pull (the analog of
    fillRemoteCommunities, /root/reference/louvain.cpp:2588-2959):

    - replicated (``sparse_plan=None``): dst ids are global (padded space);
      an all_gather replicates the community vector and full-width
      psum-reduced comm_deg/comm_size tables — O(nv_total) per chip.
    - sparse (``sparse_plan=(send_idx, ghost_sel)``): dst ids are
      extended-local (owned + ghost table); community values and attached
      community degree/size ride the phase-static ghost routing, community
      info is sharded by owner and resolved through the budgeted
      owner-reduce (cuvite_tpu/comm/exchange.py) — O(owned + ghosts).
    - two-level (``sparse_plan`` + ``ici_axis``, ISSUE 18): ``axis_name``
      is the slow DCN axis of a 2-D hybrid mesh, the plan a GROUPED one
      (``ExchangePlan.build_grouped``); community state is gathered to
      group scale on the fast ICI axis — O(nv_total / n_dcn) per chip —
      and the sparse protocol runs between groups on the DCN axis.
      Scalars reduce over both axes; the a² modularity term over DCN
      only (the group tables are ICI-replicated).

    ``info_comm``: optional FROZEN assignment used only for the community
    degree/size tables — the vertex-ordering schedule (reference -d,
    /root/reference/louvain.cpp:1535-1562) hoists the community-info
    exchange out of the color loop, so later classes see earlier classes'
    ``comm`` updates but iteration-start community info.  Replicated
    exchange only (single-shard, or SPMD via make_sharded_class_step).

    ``heavy_kernel``: optional ``(verts [Hp], dstT [D, Hp], wT [D, Hp])``
    phase-static layout (kernels/heavy_bincount.build_heavy_layout) —
    the heavy (> widths[-1] degree) residual then runs the
    community-range-tile bincount kernel instead of the per-iteration
    global sort (the ISSUE 8 promotion; single-shard replicated only —
    the kernel has no attached-size channel for the sparse exchange).
    Same gain formula, tie-break and counter0 accumulation order as the
    sorted path: labels are bit-identical on the exactness domain.
    """
    nv_local = comm.shape[0]
    wdt = vdeg.dtype
    vdt = comm.dtype

    use_sparse = sparse_plan is not None
    red_axes = (axis_name if ici_axis is None else (axis_name, ici_axis))
    with jax.named_scope("cuvite.tables"):
        if use_sparse:
            from cuvite_tpu.comm.exchange import (
                sparse_env, sparse_modularity, twolevel_env)

            assert axis_name is not None, \
                "sparse exchange requires a mesh axis"
            if ici_axis is not None:
                env = twolevel_env(comm, vdeg, sparse_plan[0], sparse_plan[1],
                                   axis_name, ici_axis, n_dcn=nshards,
                                   budget=budget, info=info_comm)
            else:
                env = sparse_env(comm, vdeg, sparse_plan[0], sparse_plan[1],
                                 axis_name, nshards=nshards, budget=budget,
                                 info=info_comm)
            comm_ref = env.comm_ext      # gather table for dst indices

            def gsum(x):
                return jax.lax.psum(x, red_axes)

            overflow = jax.lax.psum(env.overflow.astype(jnp.int32),
                                    red_axes) > 0
        else:
            env = None
            comm_ref, gsum = seg.spmd_env(comm, axis_name)
            info = comm if info_comm is None else info_comm
            comm_deg = gsum(seg.segment_sum(vdeg, info, num_segments=nv_total))  # graftlint: replicated-ok=scope=ici; replicated-exchange community degree table, flat-mesh-only (one ICI group); sparse/two-level modes ride the ghost plan instead
            comm_size = gsum(seg.segment_sum(  # graftlint: replicated-ok=scope=ici; replicated-exchange community size table, flat-mesh-only (one ICI group); sparse/two-level modes attach sizes to ghosts instead
                jnp.ones((nv_local,), dtype=vdt), info, num_segments=nv_total
            ))
            overflow = jnp.zeros((), dtype=bool)  # replicated: can't overflow

    # Community-info lookups.  Sparse mode reads values ATTACHED to the
    # referenced vertex (indexed by dst in the extended-local table);
    # replicated mode looks the community id up in the full tables.
    def slot_ay(dst_idx, ck):
        return (jnp.take(env.cdeg_ext, dst_idx) if use_sparse
                else jnp.take(comm_deg, ck))

    def slot_size(dst_idx, ck):
        return jnp.take(env.csize_ext, dst_idx) if use_sparse else None

    def own_deg(v_safe):   # comm_deg[comm[v]] for owned v
        return (jnp.take(env.cdeg_v, v_safe) if use_sparse
                else jnp.take(comm_deg, jnp.take(comm, v_safe)))

    neg_inf = jnp.array(-jnp.inf, dtype=wdt)

    # Heavy-vertex current-community weight (also their e_ix source).
    with jax.named_scope("cuvite.heavy"):
        hs, hd, hw = heavy_arrays
        use_heavy_kernel = heavy_kernel is not None
        if use_heavy_kernel:
            # Opt-in heavy path: ONE community-range-tile kernel
            # pass per iteration — no heavy sort, no per-iteration triples
            # gather.  Replicated/single-shard only: the kernel consumes the
            # dense comm_deg table (and the sparse singleton guard needs an
            # attached-size channel it does not have).
            assert not use_sparse and axis_name is None, \
                "heavy_kernel is a single-shard replicated-path layout"
            from cuvite_tpu.kernels.heavy_bincount import heavy_argmax_pallas

            hk_verts, hk_dT, hk_wT = heavy_kernel
            safe_hv = jnp.minimum(hk_verts, nv_local - 1)
            curr_h = jnp.take(comm, safe_hv)
            vdeg_h = jnp.take(vdeg, safe_hv)
            # Padding slots (dst == pad id >= nv_local) mask to nv_total: >=
            # every candidate tile's range, so they are never candidates and
            # never touch counter0 (w == 0 there anyway).
            hk_pad = hk_dT >= jnp.asarray(nv_local, hk_dT.dtype)
            cT = jnp.where(
                hk_pad, jnp.asarray(nv_total, hk_dT.dtype),
                jnp.take(comm_ref, jnp.minimum(hk_dT, nv_local - 1)))
            hk_bc, hk_bg, hk_c0 = heavy_argmax_pallas(
                cT, hk_wT.astype(wdt), comm_deg, curr_h, vdeg_h,
                jnp.take(self_loop, safe_hv), own_deg(safe_hv) - vdeg_h,
                constant, interpret=pallas_interpret)
            c0_heavy = jnp.zeros((nv_local,), dtype=wdt).at[hk_verts].set(
                hk_c0, mode="drop")
        else:
            ckey_h = jnp.take(comm_ref, hd)
            csrc_h = jnp.take(comm, jnp.minimum(hs, nv_local - 1))
            c0_heavy = seg.segment_sum(
                jnp.where(ckey_h == csrc_h, hw, jnp.zeros_like(hw)), hs,
                num_segments=nv_local,
            )

    # One pass per bucket: e_ix is row-local (every edge of a bucket vertex
    # lives in its row), so dedup + counter0 + gain + argmax all happen in a
    # single kernel over each bucket — no global counter0 prepass.
    is_pallas = (list(pallas_flags) if pallas_flags
                 else [False] * len(bucket_arrays))
    parts = []   # (verts, best_c, best_gain, counter0, best_size|None)
    for i, (verts, dst_mat, w_mat) in enumerate(bucket_arrays):
        # Kernel classes arrive transposed [D, Nb]: the width is D.
        width = dst_mat.shape[0 if is_pallas[i] else 1]
        with jax.named_scope(f"cuvite.class_w{width}"):
            safe_v = jnp.minimum(verts, nv_local - 1)
            curr = jnp.take(comm, safe_v)
            if is_pallas[i]:
                # Kernel classes arrive TRANSPOSED [D, Nb]; the gathers
                # below stay index-shaped, so the community/ay/size
                # matrices come out [D, Nb] too.  Works identically
                # single-shard and inside the shard_map body: replicated
                # mode looks candidate info up in the psum'd full tables,
                # sparse mode reads the values ATTACHED to the referenced
                # vertex (extended-local dst indices) and the kernel
                # additionally tracks the winning community's size for
                # the singleton guard.
                from cuvite_tpu.kernels.row_argmax import row_argmax_pallas

                if w_mat.dtype != wdt:   # uint8-compressed unit weights
                    w_mat = w_mat.astype(wdt)
                cmat_t = jnp.take(comm_ref, dst_mat)   # [D, Nb]
                vdeg_v = jnp.take(vdeg, safe_v)
                ayT = (jnp.take(env.cdeg_ext, dst_mat) if use_sparse
                       else jnp.take(comm_deg, cmat_t))
                szT = jnp.take(env.csize_ext, dst_mat) if use_sparse else None
                out = row_argmax_pallas(
                    cmat_t, w_mat, ayT,
                    curr, vdeg_v, jnp.take(self_loop, safe_v),
                    own_deg(safe_v) - vdeg_v, constant, szT=szT,
                    sentinel=sentinel, interpret=pallas_interpret,
                )
                if use_sparse:
                    bc, bg, c0_rows, bs = out
                    parts.append((verts, bc.astype(vdt), bg, c0_rows,
                                  bs.astype(vdt)))
                else:
                    bc, bg, c0_rows = out
                    parts.append((verts, bc.astype(vdt), bg, c0_rows, None))
                continue
            vdeg_v = jnp.take(vdeg, safe_v)
            res = _rows_chunked(w_mat, dst_mat,
                                curr, vdeg_v, jnp.take(self_loop, safe_v),
                                own_deg(safe_v) - vdeg_v,
                                constant, sentinel,
                                lambda dm: jnp.take(comm_ref, dm),
                                slot_ay, slot_size, wdt,
                                id_bound=nv_total)
            parts.append((verts, res.best_c, res.best_gain, res.counter0,
                          res.best_size))

    # Assemble per-vertex results from the per-bucket row vectors.  Bucket
    # membership is phase-static and disjoint, so with ``assemble_perm``
    # (vertex -> position in the concatenated row space; the trailing slot
    # holds the no-bucket default) assembly is three pure gathers — the
    # scatter-free path.  Without a perm (class-restricted plans) fall back
    # to scatters.
    with jax.named_scope("cuvite.assemble"):
        if assemble_perm is not None and parts:
            cat = lambda xs, d: jnp.concatenate(xs + [d])  # noqa: E731
            d1 = lambda v, dt: jnp.full((1,), v, dtype=dt)  # noqa: E731
            best_c = jnp.take(
                cat([p[1] for p in parts], d1(sentinel, vdt)), assemble_perm)
            best_gain = jnp.take(
                cat([p[2] for p in parts], neg_inf[None]), assemble_perm)
            counter0 = c0_heavy + jnp.take(
                cat([p[3] for p in parts], d1(0, wdt)), assemble_perm)
            if use_sparse:
                best_size = jnp.take(
                    cat([p[4] for p in parts], d1(0, vdt)), assemble_perm)
            else:
                best_size = None
        else:
            best_c = jnp.full((nv_local,), sentinel, dtype=vdt)
            best_gain = jnp.full((nv_local,), neg_inf, dtype=wdt)
            counter0 = c0_heavy
            best_size = (jnp.zeros((nv_local,), dtype=vdt) if use_sparse
                         else None)
            for verts, bc, bg, c0, bs in parts:
                best_c = best_c.at[verts].set(bc, mode="drop")
                best_gain = best_gain.at[verts].set(bg, mode="drop")
                counter0 = counter0.at[verts].add(c0, mode="drop")
                if use_sparse and bs is not None:
                    best_size = best_size.at[verts].set(bs, mode="drop")
        eix = counter0 - self_loop

    # ---- heavy vertices ---------------------------------------------------
    with jax.named_scope("cuvite.heavy"):
        if use_heavy_kernel:
            # Kernel results scatter to their vertices; everything else keeps
            # -inf/sentinel so the merge below is a no-op there.  The kernel's
            # no-candidate sentinel (int max of the id dtype) IS `sentinel`.
            hg = jnp.full((nv_local,), neg_inf, dtype=wdt).at[hk_verts].set(
                hk_bg, mode="drop")
            hc = jnp.full((nv_local,), sentinel, dtype=vdt).at[hk_verts].set(
                hk_bc.astype(vdt), mode="drop")
        else:
            # Sort-based candidates on the heavy edges only (the historical
            # path; sparse exchange and oversized layouts stay here).
            if use_sparse:
                src_s, ckey_s, w_s, ay_s, ts_s = \
                    seg.sort_edges_by_vertex_comm(
                        hs, ckey_h, hw, jnp.take(env.cdeg_ext, hd),
                        jnp.take(env.csize_ext, hd),
                        src_bound=nv_local + 1, key_bound=nv_total)
            else:
                src_s, ckey_s, w_s = seg.sort_edges_by_vertex_comm(
                    hs, ckey_h, hw, src_bound=nv_local + 1,
                    key_bound=nv_total)
            starts = seg.run_starts(src_s, ckey_s)
            eiy, _ = seg.run_totals(w_s, starts)
            i_s = jnp.minimum(src_s, nv_local - 1)
            comm_i = jnp.take(comm, i_s)
            valid = starts & (src_s < nv_local) & (ckey_s != comm_i)
            k_i = jnp.take(vdeg, i_s)
            a_y = ay_s if use_sparse else jnp.take(comm_deg, ckey_s)
            a_x = own_deg(i_s) - k_i
            gain = 2.0 * (eiy - jnp.take(eix, i_s)) \
                - 2.0 * k_i * (a_y - a_x) * constant
            gain = jnp.where(valid, gain, neg_inf)
            hg = seg.segment_max(gain, src_s, num_segments=nv_local,
                                 sorted_ids=True)
            at_best = valid & (gain == jnp.take(hg, i_s))
            cand_c = jnp.where(at_best, ckey_s,
                               jnp.full_like(ckey_s, sentinel))
            hc = seg.segment_min(cand_c, src_s, num_segments=nv_local,
                                 sorted_ids=True)
        heavy_better = hg > best_gain
        best_gain = jnp.where(heavy_better, hg, best_gain)
        best_c = jnp.where(heavy_better, hc, best_c)
        if use_sparse:
            chosen = at_best & (ckey_s == jnp.take(hc, i_s))
            ts_cand = jnp.where(chosen, ts_s, jnp.full_like(ts_s, sentinel))
            h_tsize = seg.segment_min(ts_cand, src_s, num_segments=nv_local,
                                      sorted_ids=True)
            best_size = jnp.where(heavy_better, h_tsize, best_size)

    # ---- select + singleton guard (louvain.cpp:2230-2241) ----------------
    with jax.named_scope("cuvite.select"):
        move = best_gain > 0.0
        best_c_safe = jnp.minimum(best_c, jnp.array(nv_total - 1, dtype=vdt))
        if use_sparse:
            t_size = best_size           # propagated from the winning slot
            c_size = env.csize_v
        else:
            t_size = jnp.take(comm_size, best_c_safe)
            c_size = jnp.take(comm_size, comm)
        guard = (t_size == 1) & (c_size == 1) & (best_c_safe > comm)
        move = move & ~guard
        target = jnp.where(move, best_c_safe, comm)
        n_moved = gsum(jnp.sum(move.astype(jnp.int32)))

    with jax.named_scope("cuvite.modularity"):
        if use_sparse:
            modularity = sparse_modularity(counter0, env.deg_local, constant,
                                           red_axes, accum_dtype,
                                           deg_axis_name=axis_name)
        else:
            modularity = seg.modularity_terms(counter0, comm_deg, constant,
                                              gsum, accum_dtype,
                                              axis_name=axis_name)
    return target, modularity, n_moved, overflow


def make_sharded_class_step(mesh, axis_name: str, n_buckets: int,
                            nv_total: int, sentinel: int, accum_dtype=None,
                            sparse=None, ordering: bool = False):
    """Jit one color class's restricted sweep as a shard_map: like
    make_sharded_bucketed_step but taking a separate ``info_comm`` — the
    community-info state the class's gains are computed against.  Coloring
    passes the committed work vector (info refreshed per class,
    /root/reference/louvain.cpp:862-901); vertex ordering passes the
    iteration-start snapshot (exchanges hoisted out of the color loop,
    louvain.cpp:1535-1562).

    ``sparse=(nshards, budget)`` runs the class sweep over the sparse ghost
    exchange (two trailing plan arrays, exactly as in
    make_sharded_bucketed_step); the 4th output is then the live
    budget-overflow flag.  Ordering's frozen info rides the exchange's
    ``info`` mode (one extra collective per class sweep)."""
    bspec = tuple((P(axis_name), P(axis_name), P(axis_name))
                  for _ in range(n_buckets))
    hspec = (P(axis_name), P(axis_name), P(axis_name))
    in_specs = [bspec, hspec, P(axis_name), P(axis_name), P(axis_name),
                P(axis_name), P(), P(axis_name)]
    out_specs = (P(axis_name), P(), P(), P())
    if sparse is not None:
        nshards, budget = sparse
        in_specs += [P(axis_name), P(axis_name)]
    else:
        nshards, budget = 1, 0

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=out_specs,
        check_vma=False,
    )
    def step(bucket_arrays, heavy_arrays, self_loop, comm, info_comm, vdeg,
             constant, perm, *plan):
        # ``ordering`` is a STATIC trait of the schedule: coloring passes
        # info == work (community info refreshed per class), so the frozen
        # info plumbing — and the sparse env's extra collective — is
        # compiled out entirely rather than detected at trace time.
        return bucketed_step(
            bucket_arrays, heavy_arrays, self_loop, comm, vdeg, constant,
            nv_total=nv_total, sentinel=sentinel, accum_dtype=accum_dtype,
            axis_name=axis_name,
            info_comm=info_comm if ordering else None,
            sparse_plan=plan if plan else None,
            nshards=nshards, budget=budget,
            assemble_perm=perm,
        )

    return jax.jit(step)


def make_sharded_bucketed_mod(mesh, axis_name: str, n_buckets: int,
                              nv_total: int, accum_dtype=None, sparse=None,
                              ici_axis=None):
    """Jit the counter0-only modularity pass as a shard_map (the SPMD
    convergence check for the class-scheduled iteration).  With
    ``sparse=(nshards, budget)`` it rides the sparse exchange and returns
    ``(modularity, overflow)``.  ``ici_axis`` (with ``sparse``) selects
    the two-level exchange on a hybrid mesh: vertex state shards over
    both axes, the grouped plan over the DCN axis only (each ICI sibling
    reads its whole group's routing rows)."""
    vspec = P(axis_name) if ici_axis is None else P((axis_name, ici_axis))
    bspec = tuple((vspec, vspec, vspec) for _ in range(n_buckets))
    hspec = (vspec, vspec, vspec)
    in_specs = [bspec, hspec, vspec, vspec, vspec, P()]
    if sparse is not None:
        nshards, budget = sparse
        in_specs += [P(axis_name), P(axis_name)]
        out_specs = (P(), P())
    else:
        nshards, budget = 1, 0
        out_specs = P()

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=out_specs,
        check_vma=False,
    )
    def mod(bucket_arrays, heavy_arrays, self_loop, comm, vdeg, constant,
            *plan):
        return bucketed_modularity(
            bucket_arrays, heavy_arrays, self_loop, comm, vdeg, constant,
            nv_total=nv_total, accum_dtype=accum_dtype, axis_name=axis_name,
            sparse_plan=plan if plan else None,
            nshards=nshards, budget=budget, ici_axis=ici_axis,
        )

    return jax.jit(mod)


def make_sharded_bucketed_step(mesh, axis_name: str, n_buckets: int,
                               nv_total: int, sentinel: int,
                               accum_dtype=None, sparse=None,
                               pallas_flags=(), pallas_interpret=False,
                               ici_axis=None):
    """Jit the bucketed sweep as a shard_map over ``axis_name``: bucket
    matrices, heavy slab and vertex state sharded along axis 0, modularity
    and move count replicated.

    ``sparse``: None for the replicated all_gather exchange, or
    ``(nshards, budget)`` to run the sparse ghost exchange — the step then
    takes two trailing plan arrays (send_idx stacked [S*S, B] and ghost_sel
    stacked [S*G], both sharded along axis 0).  The 4th output is the
    replicated budget-overflow flag (constant False without sparse).

    ``pallas_flags`` (one bool per bucket, static): flagged classes run the
    Pallas row-argmax kernel inside the shard_map body — their stacked
    dst/w matrices must be placed TRANSPOSED [S*D, Nb] (still sharded
    along axis 0, so each shard's block is the kernel's [D, Nb] layout);
    see StackedPlan.pallas_flags.  ``pallas_interpret`` runs the kernel in
    interpret mode (non-TPU backends).

    ``ici_axis`` (with ``sparse``): the two-level exchange over a hybrid
    ``(axis_name, ici_axis)`` mesh — ``axis_name`` is then the slow DCN
    axis, ``sparse=(n_dcn, budget)`` carries the GROUP count, vertex
    state shards over both axes (dcn-major, identical per-device blocks
    to the flat mesh), and the grouped plan arrays shard over the DCN
    axis only so every ICI sibling drives the same group-scale
    protocol."""
    vspec = P(axis_name) if ici_axis is None else P((axis_name, ici_axis))
    bspec = tuple((vspec, vspec, vspec) for _ in range(n_buckets))
    hspec = (vspec, vspec, vspec)
    in_specs = [bspec, hspec, vspec, vspec, vspec, P(), vspec]
    out_specs = (vspec, P(), P(), P())
    if sparse is not None:
        nshards, budget = sparse
        in_specs += [P(axis_name), P(axis_name)]
    else:
        nshards, budget = 1, 0

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=out_specs,
        check_vma=False,
    )
    def step(bucket_arrays, heavy_arrays, self_loop, comm, vdeg, constant,
             perm, *plan):
        return bucketed_step(
            bucket_arrays, heavy_arrays, self_loop, comm, vdeg, constant,
            nv_total=nv_total, sentinel=sentinel, accum_dtype=accum_dtype,
            axis_name=axis_name,
            pallas_flags=pallas_flags, pallas_interpret=pallas_interpret,
            sparse_plan=plan if plan else None,
            nshards=nshards, budget=budget, ici_axis=ici_axis,
            assemble_perm=perm,
        )

    return jax.jit(step)
