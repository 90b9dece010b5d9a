"""Delta ingestion against the resident device slab (ISSUE 17).

Live graphs mutate between requests; rebuilding the CSR + re-uploading
the slab per update throws away the device residency the fused driver
works to keep (coarsen/device.py).  This module applies validated edge
insert/delete batches to the slab **in HBM** through ONE jitted
chokepoint:

  * :class:`DeltaBatch` — a canonicalized edit batch: symmetrized like
    ``Graph.from_edges`` (each undirected insert lands as (u,v) and
    (v,u), self-loops once), duplicate inserts coalesced, deletes
    deduped, rows in ascending (src, dst) order.  Canonical form makes
    the batch — and therefore the content fingerprint lineage the
    warm-start validation hangs off — deterministic in the edit
    MULTISET, not the arrival order.
  * :func:`apply_delta_slab` — THE chokepoint (graftlint R029 keeps
    every other resident-slab mutation out of ``stream/``/``serve/``):
    deletes are located by a pure-int32 lexicographic binary search
    over the sorted slab and sentinel-retired in place (src -> nv_pad,
    w -> 0 — exactly a padding row); inserts are masked-appended into
    the slab's padding headroom at traced offset ``ne``; then the whole
    slab re-canonicalizes through the segmented-coalesce chokepoint
    (ops/segment.py::coalesced_runs, a packed sort), whose output
    contract — ascending (src, dst), duplicates summed, compacted,
    sentinel padding after — is bit-identical to what
    ``DistGraph.build`` derives from ``Graph.from_edges`` on the
    mutated edge list.  That identity is what the delta-vs-rebuild
    suite pins (tests/test_stream.py).

The pow2 slab class is preserved: the compile key set stays {(nv_pad,
ne_pad, d_pad, accum)}, all pow2, so a tenant's second same-class delta
re-enters the compiled program with zero fresh traces.  When an insert
batch overflows the padding headroom the HOST wrapper (stream/
session.py) first lifts the slab to the next pow2 class via
``coarsen.device.grow_slab`` — the spill twin of ``shrink_slab`` —
never by a dynamic reshape inside the jit.

Exactness domain: duplicate-weight sums run through the same
accumulators as coarsening, so slab weights match the host rebuild
bit-for-bit wherever run sums are exactly representable (unit/dyadic
weights — the parity suite's domain, cf. coarsen/device.py).
"""

from __future__ import annotations

import dataclasses
import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from cuvite_tpu.ops import segment as seg

# Floor on the padded delta-batch class: batches pad to
# max(next_pow2(n), DELTA_PAD_MIN) so every small batch shares one
# compiled chokepoint instance per slab class instead of one per size.
DELTA_PAD_MIN = 256


def _canon_pairs(src, dst, nv: int, what: str):
    """Validate + symmetrize an edit pair list: int64 arrays, ids in
    [0, nv); (u, v) with u != v contributes both directions, a self-loop
    once — exactly Graph.from_edges' symmetrize convention."""
    src = np.asarray(src, dtype=np.int64).ravel()
    dst = np.asarray(dst, dtype=np.int64).ravel()
    if src.shape != dst.shape:
        raise ValueError(f"{what}: src/dst length mismatch "
                         f"({src.size} vs {dst.size})")
    if src.size and (src.min() < 0 or dst.min() < 0
                     or src.max() >= nv or dst.max() >= nv):
        raise ValueError(
            f"{what}: vertex id out of range [0, {nv}) — streaming "
            "deltas mutate edges among the session's existing vertices")
    off = src != dst
    return (np.concatenate([src, dst[off]]),
            np.concatenate([dst, src[off]]), off)


@dataclasses.dataclass(frozen=True)
class DeltaBatch:
    """One canonical edge edit batch against an ``nv``-vertex graph.

    ``ins_src``/``ins_dst``/``ins_w``: coalesced symmetrized inserts in
    ascending (src, dst) order; ``del_src``/``del_dst``: deduped
    symmetrized deletes, same order.  Deletes apply to the BASE slab
    first, inserts after — so the rebuild oracle for a batch is
    ``(base_edges - deletes) + inserts``.
    """

    num_vertices: int
    ins_src: np.ndarray
    ins_dst: np.ndarray
    ins_w: np.ndarray
    del_src: np.ndarray
    del_dst: np.ndarray

    @property
    def n_ins(self) -> int:
        return int(self.ins_src.size)

    @property
    def n_del(self) -> int:
        return int(self.del_src.size)

    @staticmethod
    def from_edits(num_vertices: int, ins_src=(), ins_dst=(), ins_w=None,
                   del_src=(), del_dst=()) -> "DeltaBatch":
        nv = int(num_vertices)
        if nv <= 0:
            raise ValueError("num_vertices must be positive")
        isrc, idst, off = _canon_pairs(ins_src, ins_dst, nv, "inserts")
        n_in = off.size                       # original (pre-mirror) pairs
        if ins_w is None:
            w = np.ones(isrc.shape, dtype=np.float64)
        else:
            # Weights are given per INPUT pair; mirror like the pairs.
            w0 = np.asarray(ins_w, dtype=np.float64).ravel()
            if w0.size != n_in:
                raise ValueError(f"inserts: weight length mismatch "
                                 f"({w0.size} weights, {n_in} pairs)")
            w = np.concatenate([w0, w0[off]])
        if w.size and (not np.all(np.isfinite(w)) or np.any(w < 0)):
            raise ValueError("inserts: weights must be finite and >= 0")
        # Coalesce duplicate insert pairs (sum in f64, like from_edges)
        # and land in ascending (src, dst) order.
        if isrc.size:
            key = isrc * nv + idst
            order = np.argsort(key, kind="stable")
            key, isrc, idst, w = key[order], isrc[order], idst[order], \
                w[order]
            first = np.concatenate([[True], key[1:] != key[:-1]])
            seg_id = np.cumsum(first) - 1
            wsum = np.zeros(int(seg_id[-1]) + 1, dtype=np.float64)
            np.add.at(wsum, seg_id, w)
            isrc, idst, w = isrc[first], idst[first], wsum
        dsrc, ddst, _ = _canon_pairs(del_src, del_dst, nv, "deletes")
        if dsrc.size:
            key = dsrc * nv + ddst
            key = np.unique(key)
            dsrc, ddst = key // nv, key % nv
        return DeltaBatch(
            num_vertices=nv,
            ins_src=isrc.astype(np.int64), ins_dst=idst.astype(np.int64),
            ins_w=w.astype(np.float64),
            del_src=dsrc.astype(np.int64), del_dst=ddst.astype(np.int64))

    def digest(self) -> int:
        """Content digest of the canonical batch — folded into the
        session's fingerprint lineage (stream/session.py), so a
        warm-start against labels from a different edit history is
        refused by arithmetic, not by convention."""
        h = zlib.crc32(np.ascontiguousarray(self.ins_src).view(np.uint8))
        h = zlib.crc32(np.ascontiguousarray(self.ins_dst).view(np.uint8), h)
        h = zlib.crc32(np.ascontiguousarray(self.ins_w).view(np.uint8), h)
        h = zlib.crc32(np.ascontiguousarray(self.del_src).view(np.uint8), h)
        h = zlib.crc32(np.ascontiguousarray(self.del_dst).view(np.uint8), h)
        return h

    def padded(self, d_pad: int | None = None):
        """Device-ready pow2-padded operand arrays for
        :func:`apply_delta_slab` — pad rows carry id -1 (the chokepoint
        masks them).  One pow2 ``d_pad`` class per batch size keeps the
        compile-key set bounded."""
        from cuvite_tpu.core.types import next_pow2

        if d_pad is None:
            d_pad = max(next_pow2(max(self.n_ins, self.n_del, 1)),
                        DELTA_PAD_MIN)

        def pad_ids(a):
            out = np.full(d_pad, -1, dtype=np.int32)
            out[:a.size] = a
            return out

        iw = np.zeros(d_pad, dtype=np.float32)
        iw[:self.n_ins] = self.ins_w
        return (pad_ids(self.ins_src), pad_ids(self.ins_dst), iw,
                pad_ids(self.del_src), pad_ids(self.del_dst), d_pad)


def _lex_search(src, dst, q_src, q_dst, *, ne_pad: int):
    """First slab index whose (src, dst) row is >= each query pair,
    by a vectorized lexicographic binary search — pure int32 (the
    packed-key trick would need int64 beyond nv_pad ~2^15; R003 keeps
    64-bit dtypes off the device path)."""
    lo = jnp.zeros(q_src.shape, jnp.int32)
    hi = jnp.full(q_src.shape, ne_pad, jnp.int32)

    def body(_, c):
        lo, hi = c
        mid = (lo + hi) >> 1
        ms = jnp.take(src, mid).astype(jnp.int32)
        md = jnp.take(dst, mid).astype(jnp.int32)
        less = (ms < q_src) | ((ms == q_src) & (md < q_dst))
        return jnp.where(less, mid + 1, lo), jnp.where(less, hi, mid)

    steps = max(ne_pad.bit_length(), 1)  # ne_pad is a static python int
    lo, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return lo


@functools.partial(jax.jit, static_argnames=("nv_pad", "accum_dtype"))
def apply_delta_slab(src, dst, w, ins_src, ins_dst, ins_w, del_src,
                     del_dst, ne, *, nv_pad: int, accum_dtype=None):
    """THE resident-slab mutation chokepoint (see module docstring).

    ``src``/``dst``/``w``: the [ne_pad] canonical slab (ascending
    (src, dst), coalesced, padding src == nv_pad / dst == 0 / w == 0
    after the first ``ne`` rows).  ``ins_*``/``del_*``: [d_pad]
    canonical batch operands from :meth:`DeltaBatch.padded` (pad rows
    id == -1).  ``ne``: traced real-row count.

    Returns ``(src2, dst2, w2, ne2, del_w, n_del_hit)``: the mutated
    slab back in canonical form in the SAME [ne_pad] class, its new
    real-row count, the total weight of retired rows (the host's 2m
    fixup subtracts it; inserts add their own known mass), and how many
    deletes matched a resident edge (absent-edge deletes are no-ops,
    exactly like the rebuild oracle's set difference).
    """
    vdt = src.dtype
    wdt = w.dtype
    ne_pad = src.shape[0]

    # --- deletes: locate + sentinel-retire --------------------------------
    q_valid = del_src >= 0
    qs = jnp.where(q_valid, del_src, jnp.int32(nv_pad))
    qd = jnp.where(q_valid, del_dst, 0)
    pos = _lex_search(src, dst, qs, qd, ne_pad=ne_pad)
    pos_c = jnp.minimum(pos, ne_pad - 1)
    hit = q_valid & (jnp.take(src, pos_c).astype(jnp.int32) == qs) \
        & (jnp.take(dst, pos_c).astype(jnp.int32) == qd)
    del_w = jnp.sum(jnp.where(hit, jnp.take(w, pos_c),
                              jnp.zeros((), wdt)))
    n_del_hit = jnp.sum(hit.astype(jnp.int32))
    retire_at = jnp.where(hit, pos_c, ne_pad)     # ne_pad drops
    src = src.at[retire_at].set(
        jnp.full(retire_at.shape, nv_pad, vdt), mode="drop")
    dst = dst.at[retire_at].set(
        jnp.zeros(retire_at.shape, vdt), mode="drop")
    w = w.at[retire_at].set(jnp.zeros(retire_at.shape, wdt), mode="drop")

    # --- inserts: masked append into the padding headroom -----------------
    i_valid = ins_src >= 0
    slot = jnp.where(i_valid,
                     ne.astype(jnp.int32) + jnp.arange(
                         ins_src.shape[0], dtype=jnp.int32),
                     jnp.int32(ne_pad))
    src = src.at[slot].set(
        jnp.where(i_valid, ins_src, nv_pad).astype(vdt), mode="drop")
    dst = dst.at[slot].set(
        jnp.where(i_valid, ins_dst, 0).astype(vdt), mode="drop")
    w = w.at[slot].set(
        jnp.where(i_valid, ins_w.astype(wdt), jnp.zeros((), wdt)),
        mode="drop")

    # --- re-canonicalize through the coalesce chokepoint ------------------
    src2, dst2, w2, ne2 = seg.coalesced_runs(
        src, dst, w, nv_pad=nv_pad, accum_dtype=accum_dtype)
    return src2, dst2, w2.astype(wdt), ne2, del_w, n_del_hit


@functools.partial(jax.jit, static_argnames=("nv_pad",))
def delta_frontier(src, dst, ins_src, ins_dst, del_src, del_dst, *,
                   nv_pad: int):
    """Warm-start active set of a delta: the touched endpoints (every
    insert/delete endpoint) plus their slab neighbors — the vertices
    whose best-community argmax could have changed — instead of "all"
    (cf. the ET active-set semantics, louvain/driver.py).  Runs on the
    POST-apply slab, so inserted edges propagate and retired rows do
    not.  Returns ``(frontier [nv_pad] bool, n_frontier)``."""
    touched = jnp.zeros((nv_pad,), bool)
    for a in (ins_src, ins_dst, del_src, del_dst):
        idx = jnp.where(a >= 0, a, jnp.int32(nv_pad))
        touched = touched.at[idx].set(True, mode="drop")
    pad = src >= nv_pad
    s_c = jnp.minimum(src, nv_pad - 1).astype(jnp.int32)
    d_c = dst.astype(jnp.int32)
    hot = (jnp.take(touched, s_c) | jnp.take(touched, d_c)) & ~pad
    fr = touched
    fr = fr.at[jnp.where(hot, s_c, nv_pad)].set(True, mode="drop")
    fr = fr.at[jnp.where(hot, d_c, nv_pad)].set(True, mode="drop")
    return fr, jnp.sum(fr.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("nv_pad", "accum_dtype",
                                             "iters"))
def plp_prepass(src, dst, w, vdeg, *, nv_pad: int, accum_dtype=None,
                iters: int = 3):
    """PLP label-propagation prepass (Staudt & Meyerhenke,
    arXiv:1304.4453 — PAPERS.md): ``iters`` synchronous sweeps of the
    Louvain step with ``constant = 0``, under which the gain degenerates
    to ``2*(e_{i->y} - e_{i->x})`` — adopt the neighbor community with
    the largest incident weight, ties to the smaller id.  The cheap
    cold-start alternative the ``--warm-start plp`` arm A/Bs against
    composed-label seeding."""
    from cuvite_tpu.louvain.step import louvain_step_local

    comm0 = jnp.arange(nv_pad, dtype=jnp.int32)
    zero = jnp.zeros((), w.dtype)

    def body(_, comm):
        out = louvain_step_local(
            src, dst, w, comm, vdeg, zero, nv_total=nv_pad,
            axis_name=None, accum_dtype=accum_dtype)
        return out.target

    return jax.lax.fori_loop(0, iters, body, comm0)
