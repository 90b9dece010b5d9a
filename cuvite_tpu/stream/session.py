"""Resident streaming session: delta application + warm-start
incremental re-clustering on the device slab (ISSUE 17).

A :class:`StreamSession` owns one tenant's device-resident canonical
edge slab (the single-shard layout of DistGraph.build / the fused
driver) across its lifetime:

  * ``apply_delta`` mutates the slab in HBM through THE chokepoint
    (stream/delta.py::apply_delta_slab), tracks the 2m fixup on the
    host in f64, folds the batch digest into the session's content
    **fingerprint lineage**, and accumulates the delta **frontier**
    (touched endpoints + slab neighbors) for the next warm start.
  * ``recluster`` re-runs the clustering with a ``--warm-start`` arm:
    ``labels`` seeds phase 0 from the previous run's composed labels
    and the ET active set from the accumulated frontier (reusing the
    driver's on-device ET phase loop via ``warm_start_phase``);
    ``plp`` seeds from a label-propagation prepass (the A/B
    alternative); ``cold`` is the from-scratch arm.  Later phases run
    the fused multi-phase program on the device-coarsened slab, so the
    whole re-cluster stays device-resident like the fused driver.

Stale warm-starts are refused LOUDLY: warm labels carry the fingerprint
of the slab content they were computed against, and ``recluster`` only
accepts them when that fingerprint equals the session's pre-delta
lineage point (the content the accumulated frontier measures edits
from).  A mismatch — labels from another session, another edit history,
or a skipped delta — raises instead of silently seeding wrong
communities, mirroring the checkpoint-resume fingerprint refusal
(utils/checkpoint.py, louvain_phases --resume).
"""

from __future__ import annotations

import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from cuvite_tpu.coarsen.device import (
    device_compose_labels,
    device_coarsen_slab,
    device_renumber,
    device_weighted_degrees,
    grow_slab,
    maybe_shrink_to_class,
)
from cuvite_tpu.core.distgraph import DistGraph
from cuvite_tpu.core.types import TERMINATION_PHASE_COUNT, next_pow2
from cuvite_tpu.stream.delta import (
    DeltaBatch,
    apply_delta_slab,
    delta_frontier,
    plp_prepass,
)
from cuvite_tpu.utils.checkpoint import graph_fingerprint

WARM_MODES = ("labels", "plp", "cold")


def _fold_fingerprint(fp: int, digest: int) -> int:
    """Advance a content-fingerprint lineage by one canonical delta
    batch: deterministic in (fp, digest), so two sessions that applied
    the same edits to the same base agree, and any divergence — a
    missed batch, a different base — never collides back."""
    return zlib.crc32(np.int64(digest).tobytes(), fp & 0xFFFFFFFF) \
        ^ ((fp >> 16) << 8)


class StreamSession:
    """One tenant's resident slab + warm-start state (module docstring).

    Public state: ``src``/``dst``/``w`` (the canonical device slab),
    ``ne`` (real rows), ``nv``/``nv_pad``/``ne_pad``, ``tw2`` (2m, host
    f64), ``fingerprint`` (content lineage), ``frontier_frac`` (of the
    pending accumulated frontier).  Labels from the last ``recluster``
    are kept on host (O(V)) for warm seeding and serving replies.
    """

    def __init__(self, *, nv, nv_pad, ne_pad, ne, src, dst, w, tw2,
                 policy, fingerprint, tracer=None):
        if tracer is None:
            from cuvite_tpu.utils.trace import NullTracer

            tracer = NullTracer()
        self.nv = int(nv)
        self.nv_pad = int(nv_pad)
        self.ne_pad = int(ne_pad)
        self.ne = int(ne)
        self.src = src
        self.dst = dst
        self.w = w
        self.tw2 = float(tw2)
        self.policy = policy
        self.fingerprint = int(fingerprint)
        self.tracer = tracer
        self._labels: np.ndarray | None = None
        self._labels_fp: int | None = None
        # The lineage point the pending frontier accumulates from: warm
        # labels are valid iff their fingerprint equals this.
        self.frontier_base_fp = int(fingerprint)
        self._frontier = None           # device bool [nv_pad] or None
        self.frontier_frac = 0.0
        self.deltas_applied = 0

    # -- construction -------------------------------------------------------

    @staticmethod
    def from_graph(graph, *, tracer=None) -> "StreamSession":
        """Upload a host graph as a resident session (the returning
        tenant's ONE full-slab upload; every later visit pays the
        delta).  Same slab class floors as the fused driver, so the
        session re-enters the driver's compiled-step cache keys."""
        dg = DistGraph.build(graph, 1, min_nv_pad=4096, min_ne_pad=16384)
        sh = dg.shards[0]
        sess = StreamSession(
            nv=graph.num_vertices, nv_pad=dg.nv_pad, ne_pad=dg.ne_pad,
            ne=sh.n_real_edges,
            src=jnp.asarray(np.asarray(sh.src).astype(np.int32)),
            dst=jnp.asarray(np.asarray(sh.dst).astype(np.int32)),
            w=jnp.asarray(np.asarray(sh.w).astype(np.float32)),
            tw2=graph.total_edge_weight_twice(),
            policy=graph.policy,
            fingerprint=graph_fingerprint(graph),
            tracer=tracer)
        return sess

    # -- facts --------------------------------------------------------------

    @property
    def real_mask(self):
        return jnp.arange(self.nv_pad, dtype=jnp.int32) < jnp.int32(self.nv)

    def hbm_bytes(self) -> int:
        """Resident HBM footprint of the session (the StreamPool
        ledger's unit): the three slab arrays plus the O(nv_pad)
        frontier/mask state.  Host-side labels are not HBM."""
        return 12 * self.ne_pad + 2 * self.nv_pad

    def labels(self) -> np.ndarray | None:
        return None if self._labels is None else self._labels.copy()

    # -- delta ingestion ----------------------------------------------------

    def apply_delta(self, batch: DeltaBatch) -> dict:
        """Apply one canonical batch through the jitted chokepoint;
        returns ``{n_ins, n_del, n_del_hit, ne, frontier_frac,
        wall_s}``.  Inserts overflowing the padding headroom first lift
        the slab to the next pow2 class (grow_slab) — the only legal
        class transition, keeping the compile-key set bounded."""
        if batch.num_vertices != self.nv:
            raise ValueError(
                f"delta batch is for {batch.num_vertices} vertices; the "
                f"resident session has {self.nv}")
        t0 = time.perf_counter()
        if self.ne + batch.n_ins > self.ne_pad:
            new_ne_pad = next_pow2(self.ne + batch.n_ins)
            self.src, self.dst, self.w = grow_slab(
                self.src, self.dst, self.w, nv_pad=self.nv_pad,
                new_nv_pad=self.nv_pad, new_ne_pad=new_ne_pad)
            self.tracer.event("delta_spill", ne_pad=self.ne_pad,
                              new_ne_pad=new_ne_pad)
            self.ne_pad = new_ne_pad
        ins_s, ins_d, ins_w, del_s, del_d, _ = batch.padded()
        ins_mass = float(np.sum(batch.ins_w, dtype=np.float64))
        adt = self._accum()
        src2, dst2, w2, ne2_d, del_w_d, nhit_d = apply_delta_slab(
            self.src, self.dst, self.w,
            jnp.asarray(ins_s), jnp.asarray(ins_d), jnp.asarray(ins_w),
            jnp.asarray(del_s), jnp.asarray(del_d),
            jnp.int32(self.ne), nv_pad=self.nv_pad,
            accum_dtype=(adt if adt == "ds32" else None))
        fr_d, nfr_d = delta_frontier(
            src2, dst2, jnp.asarray(ins_s), jnp.asarray(ins_d),
            jnp.asarray(del_s), jnp.asarray(del_d), nv_pad=self.nv_pad)
        if self._frontier is not None:
            fr_d = jnp.logical_or(fr_d, self._frontier)
            nfr_d = jnp.sum(fr_d.astype(jnp.int32))
        ne2, del_w, n_hit, n_fr = jax.device_get(
            (ne2_d, del_w_d, nhit_d, nfr_d))
        self.src, self.dst, self.w = src2, dst2, w2
        self.ne = int(ne2)
        # 2m fixup on host, f64: inserts add a mass known exactly from
        # the canonical batch; deletes subtract the retired rows' slab
        # weight as measured by the chokepoint.
        self.tw2 = self.tw2 + ins_mass - float(del_w)
        if self.tw2 <= 0:
            raise ValueError("delta removed the last edge weight; an "
                             "empty graph cannot be re-clustered")
        self.fingerprint = _fold_fingerprint(self.fingerprint,
                                             batch.digest())
        self._frontier = fr_d
        self.frontier_frac = float(int(n_fr)) / float(self.nv)
        self.deltas_applied += 1
        wall = time.perf_counter() - t0
        info = {"n_ins": batch.n_ins, "n_del": batch.n_del,
                "n_del_hit": int(n_hit), "ne": self.ne,
                "frontier_frac": round(self.frontier_frac, 6),
                "wall_s": wall}
        self.tracer.event("delta", **info)
        return info

    # -- re-clustering ------------------------------------------------------

    def _accum(self) -> str:
        from cuvite_tpu.louvain.driver import _accum_name

        return _accum_name(np.dtype(np.float32), self.tw2,
                           max(self.ne, self.nv_pad))

    def recluster(self, warm: str = "labels", threshold: float = 1.0e-6,
                  max_phases: int = TERMINATION_PHASE_COUNT,
                  warm_labels=None, warm_fingerprint: int | None = None,
                  plp_iters: int = 3):
        """Re-cluster the resident slab; returns a
        ``louvain.driver.LouvainResult`` (same shape as the batch
        drivers, so golden envelopes and serving replies apply as-is).

        ``warm='labels'`` seeds phase 0 from the previous run's
        composed labels (or caller-supplied ``warm_labels`` tagged with
        ``warm_fingerprint``) and activates only the accumulated delta
        frontier; a fingerprint mismatch raises.  ``warm='plp'`` seeds
        from a ``plp_iters``-sweep label-propagation prepass;
        ``warm='cold'`` starts from identity.  Both non-label arms
        activate every real vertex.
        """
        from cuvite_tpu.louvain.driver import (
            LouvainResult,
            PhaseStats,
            warm_start_phase,
        )
        from cuvite_tpu.louvain.fused import _fused_step_call, fused_louvain
        from cuvite_tpu.louvain.precise import phase_modularity

        if warm not in WARM_MODES:
            raise ValueError(f"unknown warm-start arm {warm!r}; "
                             f"use one of {WARM_MODES}")
        t0 = time.perf_counter()
        nv, nv_pad = self.nv, self.nv_pad
        adt = self._accum()
        real_mask = self.real_mask
        vdeg = device_weighted_degrees(self.src, self.w, nv_pad=nv_pad)
        constant = jnp.asarray(1.0 / self.tw2, dtype=jnp.float32)

        if warm == "labels":
            labels = warm_labels if warm_labels is not None \
                else self._labels
            fp = warm_fingerprint if warm_labels is not None \
                else self._labels_fp
            if labels is None:
                raise ValueError(
                    "warm-start 'labels' needs resident labels: run a "
                    "cold (or plp) recluster first, or pass warm_labels")
            if fp != self.frontier_base_fp:
                raise ValueError(
                    f"stale warm-start refused: labels carry content "
                    f"fingerprint {fp:#x} but the session's pre-delta "
                    f"lineage is {self.frontier_base_fp:#x} — these "
                    "labels were not computed against the slab the "
                    "pending deltas edited (wrong session, wrong base, "
                    "or a skipped batch); re-cluster cold instead")
            comm0_np = np.arange(nv_pad, dtype=np.int32)
            comm0_np[:nv] = np.asarray(labels, dtype=np.int32)[:nv]
            comm0 = jnp.asarray(comm0_np)
            active0 = (self._frontier & real_mask) \
                if self._frontier is not None \
                else jnp.zeros((nv_pad,), bool)
        elif warm == "plp":
            comm0 = plp_prepass(self.src, self.dst, self.w, vdeg,
                                nv_pad=nv_pad, accum_dtype=adt,
                                iters=int(plp_iters))
            active0 = real_mask
        else:
            comm0 = jnp.arange(nv_pad, dtype=jnp.int32)
            active0 = real_mask

        extra = (self.src, self.dst, self.w, vdeg, constant)
        sid = self.tracer.begin_span("recluster", warm=warm) \
            if hasattr(self.tracer, "begin_span") else None
        labels_d, mod0_d, iters0_d, _ovf, _conv = warm_start_phase(
            extra, comm0, threshold, active0,
            call=_fused_step_call(nv_pad, adt), nv_real=nv)

        # Device coarsen + label composition, then the fused program for
        # every remaining phase — the _run_fused pattern, one level deep
        # (post-phase-0 graphs are coarse).
        dmap, nc_d = device_renumber(labels_d, real_mask, nv_pad=nv_pad)
        comm_all_d = device_compose_labels(
            dmap, labels_d, jnp.arange(nv, dtype=labels_d.dtype))
        acc = adt if adt == "ds32" else None
        csrc, cdst, cw, _dm, _nc, ne2_d = device_coarsen_slab(
            self.src, self.dst, self.w, labels_d, real_mask,
            nv_pad=nv_pad, accum_dtype=acc, dense_map=dmap, nc=nc_d)
        nc, ne2, mod0, iters0 = jax.device_get(  # graftlint: disable=R010 — phase-scalar sync, O(1), the streaming analog of the fused driver's per-call stat fetch
            (nc_d, ne2_d, mod0_d, iters0_d))
        nc, ne2, iters0 = int(nc), int(ne2), int(iters0)
        csrc, cdst, cw, cnv_pad, cne_pad = maybe_shrink_to_class(
            csrc, cdst, cw, nc=nc, ne2=ne2, nv_pad=nv_pad,
            ne_pad=self.ne_pad)

        phases = [PhaseStats(phase=0, modularity=float(mod0),
                             iterations=iters0, num_vertices=nv,
                             num_edges=self.ne, seconds=0.0)]
        tot_iters = iters0
        mask2 = jnp.arange(cnv_pad, dtype=jnp.int32) < jnp.int32(nc)
        max_p2 = max(int(max_phases) - 1, 1)
        ths = np.full(max_p2, threshold, dtype=np.float32)
        out = fused_louvain(
            csrc, cdst, cw, jnp.asarray(ths), constant, mask2,
            nv_pad=cnv_pad, max_phases=max_p2, accum_dtype=adt,
            cycling=False, prev_mod0=np.float32(mod0))
        labels2 = out[0]
        n_ph2, iters2, mod_hist, iter_hist, nc_hist = jax.device_get(  # graftlint: disable=R010 — phase-scalar sync, O(max_phases)
            (out[2], out[3], out[4], out[5], out[6]))
        n_ph2, iters2 = int(n_ph2), int(iters2)
        tot_iters += iters2
        nv_p = nc
        for p in range(n_ph2):
            phases.append(PhaseStats(
                phase=len(phases), modularity=float(mod_hist[p]),
                iterations=int(iter_hist[p]), num_vertices=nv_p,
                num_edges=ne2, seconds=0.0))
            nv_p = int(nc_hist[p])
        dmap2, nc2_d = device_renumber(labels2, mask2, nv_pad=cnv_pad)
        comm_all_d = device_compose_labels(dmap2, labels2, comm_all_d)
        comm_all = np.asarray(comm_all_d).astype(np.int64)  # graftlint: disable=R010 — the final label gather, O(V), same allowlist as the fused driver's
        num_comms = int(comm_all.max()) + 1 if comm_all.size else 0

        dgq = DistGraph.from_device_slab(
            csrc, cdst, cw, num_vertices=nc, num_edges=ne2,
            nv_pad=cnv_pad, ne_pad=cne_pad, policy=self.policy,
            total_weight_twice=self.tw2)
        final_q = phase_modularity(dgq, np.asarray(labels2),  # graftlint: disable=R010 — final labels, O(coarse V), re-used on device by the ds pass
                                   device_slab=(csrc, cdst, cw))

        wall = time.perf_counter() - t0
        for st in phases:
            st.seconds = wall / max(len(phases), 1)
        # Labels now describe the CURRENT content; the frontier resets.
        self._labels = comm_all
        self._labels_fp = self.fingerprint
        self.frontier_base_fp = self.fingerprint
        self._frontier = None
        frontier_frac = self.frontier_frac
        self.frontier_frac = 0.0
        if sid is not None:
            self.tracer.end_span(sid, wall_s=wall, warm=warm,
                                 q=float(final_q),
                                 frontier_frac=round(frontier_frac, 6),
                                 iterations=tot_iters)
        else:
            self.tracer.event("recluster", warm=warm, wall_s=wall,
                              q=float(final_q), iterations=tot_iters)
        return LouvainResult(
            communities=comm_all, modularity=float(final_q),
            phases=phases, total_iterations=tot_iters,
            total_seconds=wall, convergence=[])
