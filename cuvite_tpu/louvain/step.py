"""One Louvain iteration as a pure, jittable SPMD function.

Maps the reference's per-iteration pipeline
(/root/reference/louvain.cpp:471-574) onto dense TPU ops:

  fillRemoteCommunities  (louvain.cpp:2588-2959)  -> lax.all_gather of the
      sharded community vector (communities of ghost tails become plain
      gathers from the replicated copy)
  distExecuteLouvainIteration (louvain.cpp:2246-2382) -> edge-parallel
      sort + segment-reduce + segment-argmax
  distUpdateLocalCinfo / updateRemoteCommunities (louvain.cpp:2539-2552,
      :2983-3116) -> community size/degree are *recomputed* each step as
      segment sums + psum, which is cheaper than replaying the reference's
      4-case atomic delta protocol and cannot drift
  distComputeModularity (louvain.cpp:2433-2481) -> two sums + psum

Gain formula, argmax tie-breaks and the singleton-swap guard replicate
distGetMaxIndex exactly (/root/reference/louvain.cpp:2185-2244):

    gain(i -> y) = 2*(e_{i->y} - e_{i->x}) - 2*k_i*(a_y - a_x) / (2m)

with e_{i->x} excluding self-loops, a_x = deg(x) - k_i, a_y = deg(y); only
strictly positive gains move a vertex; ties break to the smaller community id;
two singletons never merge "upward" (maxIndex > currComm blocked).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from cuvite_tpu.ops import segment as seg


class StepOut(NamedTuple):
    target: jax.Array     # [nv_local] new community per owned vertex
    modularity: jax.Array  # scalar: modularity of the INPUT assignment
    n_moved: jax.Array     # scalar int32: vertices that changed community


def louvain_step_local(
    src,          # [ne_pad] int: LOCAL source index; pad = nv_local
    dst,          # [ne_pad] int: GLOBAL (padded-space) tail id; pad = 0, w = 0
    w,            # [ne_pad] weight
    comm_local,   # [nv_local] int: community id (padded-global space)
    vdeg_local,   # [nv_local] weight: k_i
    constant,     # scalar: 1 / (2m)
    *,
    nv_total: int,
    axis_name: str | None = None,
    accum_dtype=None,
) -> StepOut:
    """One synchronous Louvain sweep over this shard's vertices.

    Pure SPMD: when ``axis_name`` is given the function runs inside
    shard_map over a 1-D mesh and communicates via all_gather/psum; with
    ``axis_name=None`` it is the single-shard program (comm_local is the full
    community vector).
    """
    nv_local = comm_local.shape[0]
    wdt = w.dtype
    vdt = comm_local.dtype
    sentinel = jnp.iinfo(vdt).max

    comm_full, gsum = seg.spmd_env(comm_local, axis_name)
    if axis_name is None:
        base = 0
    else:
        base = jax.lax.axis_index(axis_name).astype(vdt) * nv_local

    # --- community info: size + weighted degree, recomputed fresh ---------
    comm_deg = gsum(
        seg.segment_sum(vdeg_local, comm_local, num_segments=nv_total)  # graftlint: replicated-ok=scope=ici; replicated-exchange community degree table (sort engine is flat-mesh-only; a flat mesh is one ICI group)
    )
    comm_size = gsum(
        seg.segment_sum(  # graftlint: replicated-ok=scope=ici; replicated-exchange community size table (sort engine is flat-mesh-only; a flat mesh is one ICI group)
            jnp.ones((nv_local,), dtype=vdt), comm_local, num_segments=nv_total
        )
    )

    # --- per-edge community keys ------------------------------------------
    src_c = jnp.minimum(src, nv_local - 1)  # clamp padding for safe gathers
    csrc = jnp.take(comm_local, src_c)              # community of edge source
    ckey = jnp.take(comm_full, dst)                 # community of edge tail
    src_global = src.astype(vdt) + base

    # weight to current community (incl. self-loops) and self-loop weight
    # (cf. counter[0] / selfLoop, louvain.cpp:2288-2296, :2396-2427)
    to_curr = jnp.where(ckey == csrc, w, jnp.zeros_like(w))
    counter0 = seg.segment_sum(to_curr, src, num_segments=nv_local, sorted_ids=True)
    self_w = jnp.where(dst == src_global, w, jnp.zeros_like(w))
    self_loop = seg.segment_sum(self_w, src, num_segments=nv_local, sorted_ids=True)
    eix = counter0 - self_loop

    # --- neighbor-community aggregation: sort + run segment sums ----------
    src_s, ckey_s, w_s = seg.sort_edges_by_vertex_comm(
        src, ckey, w, src_bound=nv_local + 1, key_bound=nv_total)
    starts = seg.run_starts(src_s, ckey_s)
    eiy, _ = seg.run_totals(w_s, starts)

    i_s = jnp.minimum(src_s, nv_local - 1)
    comm_i = jnp.take(comm_local, i_s)
    valid = starts & (src_s < nv_local) & (ckey_s != comm_i)

    # --- dQ for every candidate run ---------------------------------------
    k_i = jnp.take(vdeg_local, i_s)
    a_y = jnp.take(comm_deg, ckey_s)
    a_x = jnp.take(comm_deg, comm_i) - k_i
    gain = 2.0 * (eiy - jnp.take(eix, i_s)) - 2.0 * k_i * (a_y - a_x) * constant
    neg_inf = jnp.array(-jnp.inf, dtype=wdt)
    gain = jnp.where(valid, gain, neg_inf)

    # --- per-vertex argmax with tie-break to smaller community id ---------
    best_gain = seg.segment_max(gain, src_s, num_segments=nv_local, sorted_ids=True)
    is_best = valid & (gain == jnp.take(best_gain, i_s))
    cand_c = jnp.where(is_best, ckey_s, jnp.full_like(ckey_s, sentinel))
    best_c = seg.segment_min(cand_c, src_s, num_segments=nv_local, sorted_ids=True)

    move = best_gain > 0.0
    best_c_safe = jnp.minimum(best_c, jnp.array(nv_total - 1, dtype=vdt))
    # singleton-swap guard (louvain.cpp:2240-2241)
    t_size = jnp.take(comm_size, best_c_safe)
    c_size = jnp.take(comm_size, comm_local)
    guard = (t_size == 1) & (c_size == 1) & (best_c_safe > comm_local)
    move = move & ~guard
    target = jnp.where(move, best_c_safe, comm_local)

    # --- modularity of the INPUT assignment (louvain.cpp:2433-2481) -------
    modularity = seg.modularity_terms(counter0, comm_deg, constant, gsum,
                                      accum_dtype, axis_name=axis_name)

    n_moved = gsum(jnp.sum(move.astype(jnp.int32)))  # graftlint: width-ok=move is per-VERTEX (nv_pad <= 2^28 rows, sum <= 2^28 < 2^31); the slab-extent tag is argmax-index over-approximation, not a real edge-extent reduction
    return StepOut(target=target, modularity=modularity, n_moved=n_moved)


def make_sharded_step(mesh: Mesh, axis_name: str, nv_total: int,
                      accum_dtype=None):
    """Build the jitted multi-chip step: edges + state sharded over
    ``axis_name``, modularity replicated."""

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name), P(axis_name),
                  P(axis_name), P()),
        out_specs=(P(axis_name), P(), P(), P()),
        check_vma=False,
    )
    def step(src, dst, w, comm, vdeg, constant):
        out = louvain_step_local(
            src, dst, w, comm, vdeg, constant,
            nv_total=nv_total, axis_name=axis_name, accum_dtype=accum_dtype,
        )
        # Uniform step contract: (target, modularity, n_moved, overflow);
        # the replicated exchange can never overflow.
        return out.target, out.modularity, out.n_moved, jnp.zeros((), bool)

    return jax.jit(step)


def make_single_step(nv_total: int, accum_dtype=None):
    """Jitted single-device step (mesh of one)."""

    def step(src, dst, w, comm, vdeg, constant):
        out = louvain_step_local(
            src, dst, w, comm, vdeg, constant,
            nv_total=nv_total, axis_name=None, accum_dtype=accum_dtype,
        )
        return out.target, out.modularity, out.n_moved, jnp.zeros((), bool)

    return jax.jit(step)
