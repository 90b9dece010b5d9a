"""Dense binned segmented-coalesce for the inter-phase relabel+coalesce
(the device-coarsening sort tax, ROADMAP open item 4 / ISSUE 8).

Role.  ``coarsen/device.py::device_coarsen_slab`` must turn the
relabeled edge slab (dense endpoint ids < nc, padding src == nv_pad)
into one row per distinct (src, dst) pair, rows in ascending (src, dst)
order compacted into the slab prefix, duplicate weights summed.  The
historical workhorse is a full-slab packed sort + run detection
(ops/segment.py) — and at benchmark scale the (src, dst) key needs
2*log2(nv_pad) > 31 bits, so the int32 packing cannot engage and the
sort degrades to XLA's slowest variadic comparator path: the measured
65 s coarsen_s of BASELINE.md round-7.  GPU Louvain implementations do
this aggregation step by BINNING, not sorting (Naim et al.,
arXiv:1805.10904 bin neighbor weights by community; the shared-memory
line treats aggregation as the dominant phase once moves are fast,
Staudt & Meyerhenke, arXiv:1304.4453).

This module is the TPU translation: the (src, dst) key domain is a
dense [nv_pad, nv_pad] grid, bin-accumulated (weight sum + run presence
count) in one pass — ascending flat index order over the accumulator
IS the sorted (src, dst) run order, so the coalesced prefix is emitted
directly with one cumsum + scatter and no sorted copy of the slab ever
exists.

Engines, selected STATICALLY per slab class (``coalesce_engine``):

* ``'xla'`` — the dense bin-accumulate as ONE O(ne) scatter-add over
  the flat key domain (``seg_coalesce_xla``).  Compiles on every
  backend.
* ``'msd'`` / ``'hash'`` — the big-class engines (below and
  ops/segment.py).
* ``'sort'`` — the sanctioned packed-sort fallback chokepoint
  (ops/segment.py::coalesced_runs), and the DEFAULT until the staged
  chip A/B promotes a dense engine (see ``coalesce_engine`` for the
  measured CPU rationale).  Slab classes whose key domain exceeds the
  accumulator budget (nv_pad > SEG_COALESCE_MAX_NV), and every ds32
  run-sum request (the pair arithmetic needs the sorted segmented
  form), degrade here in every mode — with coverage reported in the
  bench record (``coalesce_kernel``), mirroring the PALLAS_MAX_WIDTH
  degrade-with-coverage pattern.

Exactness.  The dense engines sum duplicate weights in SLAB order
(scatter order), the sort path in sorted-run order; the two are
bit-identical wherever run sums are exactly representable — unit and
dyadic weights, the same documented exactness domain as the host-f64
oracle contract in coarsen/device.py.  Run PRESENCE (the emitted row
set, hence offsets/tails) is exact in every mode, including real
zero-weight edges (counted by presence, never by weight).
"""

from __future__ import annotations

import os

import jax.numpy as jnp

# Widest slab class the dense accumulator covers: the flat key domain is
# nv_pad^2 slots (f32 + i32), i.e. 128 MiB at the 4096 default — late
# coarsened phases, where the reference's own cost model says binning
# wins (tools/heavy_kernel_design.md).  Raising it quadruples the
# accumulator per step.
DEFAULT_MAX_NV = 4096

# Hard ceiling on the dense engines' vertex space: the flat (src, dst)
# key is packed as (src << kbits) | dst in int32, so 2 * kbits must
# stay <= 31 — nv_pad <= 2^15, a 2^30-slot flat domain.  This is the
# same number _env_max_nv caps CUVITE_SEG_COALESCE_MAX_NV at, restated
# as a fail-loud raise-guard so a caller bypassing coalesce_engine can
# never wrap the packed key (widthcheck R026/R027 read it as the
# eligibility predicate; tools/width_audit.py proves the one-past
# class raises, W002).
FLAT_NV_MAX = 1 << 15


def _env_max_nv() -> int:
    from cuvite_tpu.utils.envknob import env_int

    # 32768^2 flat keys is the int32 packing ceiling (2^30) and an
    # 8 GiB accumulator — anything above is certainly a typo.
    return env_int("CUVITE_SEG_COALESCE_MAX_NV", DEFAULT_MAX_NV,
                   maximum=32768)


def coalesce_engine(nv_pad: int, accum_dtype=None) -> str:
    """THE static engine decision for one slab class: 'xla', 'msd',
    'hash' or 'sort'.  Read per CALL by the drivers (not per trace — the result is
    a static argument of device_coarsen_slab, so env toggles take effect
    on the next phase without stale-trace hazards).

    CUVITE_SEG_COALESCE: '' (default) — the packed-sort path; 'xla' /
    'dense' / '1' — the XLA dense engine where the class fits; 'msd' — the two-pass int32 MSD
    sort (ops/segment.sort_edges_msd: never degrades — ds32-capable,
    no domain cap, and identical to 'sort' below the 31-bit pack
    ceiling); 'hash' — the hash-slot coalesce below (explicit
    accumulators route to 'msd': its tables sum in the weight dtype);
    '0' / 'sort' — explicit sort pin.  Ineligible classes (domain over
    budget, ds32) degrade the DENSE modes to 'sort', with coverage
    reported by the drivers (the PALLAS_MAX_WIDTH
    degrade-with-coverage pattern).

    Why default-off (measured, this rig, 24-core CPU backend): every
    ELIGIBLE class (nv_pad <= 4096 -> 25-bit key) already rides the
    packed int32 single-key sort, which beat the dense engines ~4.7x at
    (nv_pad 4096, ne_pad 2^20) — XLA CPU scatters cost ~micro-seconds
    per element.  The classes paying the real sort tax (nv_pad >= 2^16,
    where kbits+sbits > 31 degrades lax.sort to the variadic comparator)
    have a key domain no dense accumulator can hold.  So on CPU the sort
    IS the best coalesce at every class; whether the dense engine wins
    on the chip is not measured yet (ROADMAP Queue 3 item 2).
    """
    mode = os.environ.get("CUVITE_SEG_COALESCE", "").strip().lower()
    if mode in ("", "0", "false", "sort"):
        return "sort"
    if mode not in ("1", "true", "dense", "xla", "msd", "hash"):
        # A typo'd pin must never silently measure the wrong engine
        # (the CUVITE_EXCHANGE_CUTOVER precedent): warn, keep the
        # default.
        import warnings

        warnings.warn(
            f"unrecognized CUVITE_SEG_COALESCE={mode!r} (want sort/0, "
            "xla/dense/1, msd, or hash); using the default "
            "'sort'", stacklevel=2)
        return "sort"
    if mode == "msd":
        # The msd sort shares the sorted-runs tail with 'sort': every
        # accumulator (ds32 included) and every class is legal.
        return "msd"
    if mode == "hash":
        # Hash tables sum in the weight dtype in slab order: explicit
        # accumulators take the msd SORTING path instead (same order as
        # 'sort', so ds32 pair sums stay exact) rather than plain
        # 'sort' — the operator asked for a big-class engine.
        return "hash" if accum_dtype is None else "msd"
    if accum_dtype is not None:
        # Any explicit accumulator degrades to sort: ds32 needs the
        # sorted segmented pair arithmetic (ops/exactsum), and a wider
        # plain dtype would be silently narrowed by the dense
        # accumulators (they sum in the weight dtype only).
        return "sort"
    if nv_pad > _env_max_nv():
        return "sort"
    return "xla"


def seg_coalesce_xla(src, dst, w, *, nv_pad: int):
    """Dense (weight, count) accumulators of the relabeled slab: one
    O(ne) scatter-add over the flat [nv_pad * nv_pad] key domain.
    src/dst: [ne_pad] int ids < nv_pad (padding src == nv_pad, w == 0);
    returns (acc [nv_pad, nv_pad] of w.dtype, cnt [nv_pad, nv_pad]
    int32) — feed :func:`emit_coalesced`."""
    assert nv_pad & (nv_pad - 1) == 0, nv_pad  # flat packing needs pow2
    if nv_pad > FLAT_NV_MAX:
        raise ValueError(
            f"seg_coalesce_xla: nv_pad = {nv_pad} over FLAT_NV_MAX = "
            f"{FLAT_NV_MAX}: the int32 flat (src << kbits) | dst key "
            "would overflow — coalesce_engine routes this class to "
            "'sort'")
    kbits = (nv_pad - 1).bit_length()
    real = src < nv_pad
    flat = jnp.where(
        real,
        (src.astype(jnp.int32) << kbits) | dst.astype(jnp.int32),
        jnp.int32(nv_pad * nv_pad),  # out of bounds -> dropped
    )
    acc = jnp.zeros((nv_pad * nv_pad,), dtype=w.dtype).at[flat].add(
        jnp.where(real, w, jnp.zeros_like(w)), mode="drop")
    cnt = jnp.zeros((nv_pad * nv_pad,), dtype=jnp.int32).at[flat].add(
        real.astype(jnp.int32), mode="drop")
    return acc.reshape(nv_pad, nv_pad), cnt.reshape(nv_pad, nv_pad)


def emit_coalesced(acc, cnt, *, ne_pad: int, src_dtype, dst_dtype):
    """Compact the dense accumulators into the coalesced slab prefix.

    Ascending flat (src * nv_pad + dst) order IS the sorted (src, dst)
    run order, so the emitted prefix is bit-identical (offsets, tails —
    and weights on the exactness domain) to the packed-sort path's.
    Returns (src2, dst2, w2, ne2) in the [ne_pad] class: real rows in
    [0, ne2), padding (src == nv_pad, dst == 0, w == 0) after.
    """
    nv_pad = acc.shape[0]
    assert nv_pad & (nv_pad - 1) == 0, nv_pad  # slab classes are pow2
    kbits = (nv_pad - 1).bit_length()
    flat_w = acc.reshape(-1)
    present = cnt.reshape(-1) > 0
    ne2 = jnp.sum(present.astype(jnp.int32))
    pos = jnp.cumsum(present.astype(jnp.int32)) - 1
    slot = jnp.where(present, pos, ne_pad)  # absent keys drop
    idx = jnp.arange(nv_pad * nv_pad, dtype=jnp.int32)  # graftlint: width-ok=flat key domain is caller-gated to nv_pad <= FLAT_NV_MAX = 2^15 (coalesce_engine policy + the seg_coalesce_xla raise-guard), so nv_pad^2 <= 2^30 fits int32
    src2 = jnp.full((ne_pad,), nv_pad, src_dtype).at[slot].set(
        (idx >> kbits).astype(src_dtype), mode="drop")
    dst2 = jnp.zeros((ne_pad,), dst_dtype).at[slot].set(
        (idx & (nv_pad - 1)).astype(dst_dtype), mode="drop")
    w2 = jnp.zeros((ne_pad,), flat_w.dtype).at[slot].set(flat_w,
                                                         mode="drop")
    return src2, dst2, w2, ne2


def coalesce_slab(src, dst, w, *, nv_pad: int):
    """One dense segmented-coalesce: accumulate + emit (the 'xla'
    engine; the 'sort' chokepoint lives in ops/segment.coalesced_runs,
    which dispatches here)."""
    acc, cnt = seg_coalesce_xla(src, dst, w, nv_pad=nv_pad)
    return emit_coalesced(acc, cnt, ne_pad=src.shape[0],
                          src_dtype=src.dtype, dst_dtype=dst.dtype)


# ---------------------------------------------------------------------------
# Hash-slot coalesce (the big-class engine of ISSUE 19): K static slots
# per src — a [nv_pad * K] table instead of the dense [nv_pad^2] domain,
# so classes FLAT_NV_MAX rules out (nv_pad >= 2^16) stay in one O(ne)
# scatter pass.  A slot receiving two distinct dst keys cannot emit;
# collision detection is DEVICE-side (scatter-min/max of dst per slot)
# and the caller (ops/segment.coalesced_runs) retries the slab through
# the msd-sorted tail inside lax.cond — no host sync, bit-identical to
# the sort engines either way.

# Table ceiling: the flat src * K + slot index is int32 and the
# emission cumsum counts table slots, so nv_pad * K stays <= 2^30 (the
# SLAB_NE_MAX discipline); the rank matrix below adds a [nv_pad, K, K]
# transient, so K is further bounded to keep it ~2^28 elements.
HASH_TABLE_MAX = 1 << 30
HASH_RANK_MAX = 1 << 28
_HASH_MULT = 2654435761  # Knuth's 2^32 / phi multiplicative constant


def hash_slots(nv_pad: int, ne_pad: int) -> int:
    """STATIC slot count per src for one slab class: pow2, derived from
    the class's mean degree (~4x headroom so light tails rarely
    collide), floored at 16, capped by nv_pad and the table/rank element
    budgets.  CUVITE_HASH_SLOTS overrides (still clamped pow2) — the
    A/B sweep knob."""
    from cuvite_tpu.utils.envknob import env_int

    k = env_int("CUVITE_HASH_SLOTS", 0, minimum=0, maximum=1 << 12)
    if k <= 0:
        avg = max(ne_pad // max(nv_pad, 1), 1)
        k = min(nv_pad, max(16, 4 * avg))
    k = 1 << max(int(k - 1).bit_length(), 0)  # pow2 ceiling
    while k > 1 and (nv_pad * k > HASH_TABLE_MAX
                     or nv_pad * k * k > HASH_RANK_MAX):
        k >>= 1
    return k


def hash_accumulate(src, dst, w, *, nv_pad: int, k: int):
    """One O(ne) scatter pass over the [nv_pad * K] slot table.  src/dst:
    [ne_pad] ids < nv_pad (padding src == nv_pad, w == 0); returns
    ``(wsum, cnt, dmin, dmax)`` flat [nv_pad * K] tables — weight sum,
    run presence count, and the min/max dst seen per slot (equal iff the
    slot is collision-free)."""
    assert k & (k - 1) == 0, k
    real = src < nv_pad
    if k == 1:
        slot = jnp.zeros(src.shape, jnp.int32)
    else:
        log2k = (k - 1).bit_length()
        slot = (dst.astype(jnp.uint32) * jnp.uint32(_HASH_MULT)
                >> (32 - log2k)).astype(jnp.int32)
    flat = jnp.where(real, src.astype(jnp.int32) * k + slot,
                     jnp.int32(nv_pad * k))  # graftlint: width-ok=hash_slots caps nv_pad * k at HASH_TABLE_MAX = 2^30, int32-safe
    d32 = dst.astype(jnp.int32)
    big = jnp.int32(nv_pad)  # > every real dst
    zero_w = jnp.zeros_like(w)
    wsum = jnp.zeros((nv_pad * k,), w.dtype).at[flat].add(
        jnp.where(real, w, zero_w), mode="drop")
    cnt = jnp.zeros((nv_pad * k,), jnp.int32).at[flat].add(
        real.astype(jnp.int32), mode="drop")
    dmin = jnp.full((nv_pad * k,), big).at[flat].min(
        jnp.where(real, d32, big), mode="drop")
    dmax = jnp.zeros((nv_pad * k,), jnp.int32).at[flat].max(
        jnp.where(real, d32, jnp.int32(0)), mode="drop")
    return wsum, cnt, dmin, dmax


def hash_emit(wsum, cnt, dmin, *, nv_pad: int, ne_pad: int, k: int,
              src_dtype, ckey_dtype):
    """Compact a collision-free slot table into the coalesced slab
    prefix, rows in ascending (src, dst) order — bit-identical (offsets,
    tails, and weights on the exactness domain) to the sorted paths.

    Within one src the occupied slots hold provably DISTINCT dst (equal
    dst hash to one slot), so the dst-ascending order inside each row is
    recovered SORT-FREE by an O(K^2) rank — this module sits inside
    graftlint R013's no-sort scope, and K is a small static constant,
    not a slab dimension.  Empty slots carry the sentinel nv_pad and
    rank after every real dst; sentinel ties break by slot index so the
    ranks form a permutation and the reordering scatter is exact."""
    dst_t = jnp.where(cnt > 0, dmin, jnp.int32(nv_pad)) \
        .reshape(nv_pad, k)
    w_t = jnp.where(cnt.reshape(nv_pad, k) > 0, wsum.reshape(nv_pad, k),
                    jnp.zeros_like(wsum.reshape(nv_pad, k)))
    sl = jnp.arange(k, dtype=jnp.int32)
    before = (dst_t[:, :, None] > dst_t[:, None, :]) | (
        (dst_t[:, :, None] == dst_t[:, None, :])
        & (sl[None, :, None] > sl[None, None, :]))
    rank = jnp.sum(before, axis=2, dtype=jnp.int32)  # [nv_pad, k]
    row = jnp.arange(nv_pad, dtype=jnp.int32)[:, None]
    ordered_d = jnp.full((nv_pad, k), nv_pad, jnp.int32) \
        .at[row, rank].set(dst_t)
    ordered_w = jnp.zeros((nv_pad, k), w_t.dtype).at[row, rank].set(w_t)
    flat_d = ordered_d.reshape(-1)
    flat_w = ordered_w.reshape(-1)
    present = flat_d < nv_pad
    # Ascending (row, rank) order IS ascending (src, dst): the standard
    # cumsum compaction (emit_coalesced) lands the prefix directly.
    # Distinct pairs <= real edges <= ne_pad, so pos never overflows the
    # output class even when nv_pad * k > ne_pad.
    n = jnp.sum(present.astype(jnp.int32))
    pos = jnp.cumsum(present.astype(jnp.int32)) - 1
    slot = jnp.where(present, pos, ne_pad)  # absent keys drop
    srcs = jnp.repeat(jnp.arange(nv_pad, dtype=jnp.int32), k)
    src_c = jnp.full((ne_pad,), nv_pad, src_dtype).at[slot].set(
        srcs.astype(src_dtype), mode="drop")
    ckey_c = jnp.zeros((ne_pad,), ckey_dtype).at[slot].set(
        flat_d.astype(ckey_dtype), mode="drop")
    w_c = jnp.zeros((ne_pad,), flat_w.dtype).at[slot].set(flat_w,
                                                          mode="drop")
    return src_c, ckey_c, w_c, n
