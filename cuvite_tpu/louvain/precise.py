"""Per-phase precise modularity (double-single accumulation on device).

The reference reports per-phase modularity accumulated in C++ double
(/root/reference/louvain.cpp:2433-2481).  Here the per-ITERATION
convergence check stays f32 (error ~6e-8, far under every threshold), and
the value REPORTED per phase is recomputed once on the phase's final
assignment with double-single arithmetic (cuvite_tpu/ops/exactsum.py):
~2^-43 relative error using only f32 ops, no x64 mode, no extra memory
beyond one O(E) pass.

Two execution paths, chosen by where the edge slab already lives:

- device (``device_slab`` given, single shard): one jitted ds pass over the
  RESIDENT slab — used by the 'sort' engine, whose src/dst/w are already on
  device; only the [nv_pad] assignment is uploaded.  NOTE: the pass's
  transients are O(E); callers must not upload a second slab copy just for
  this (the bucketed engine deliberately keeps no slab on device).
- host (default): the phase-end assignment is already host-side, so the
  value is computed there in f64 with zero device memory — O(E) host work
  once per phase, while the device waits.  At ``native.MIN_NATIVE_EDGES``
  edges and above, one fused native pass over the CSR sums the terms
  (``native.modularity_terms``) and Q is finished with the oracle's own
  expression: bit-identical to the numpy oracle (evaluate/modularity.py)
  for integer-weight graphs, equal to f64 rounding otherwise.  Below it,
  or without the library, the numpy oracle itself runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from cuvite_tpu import native
from cuvite_tpu.ops import exactsum as ds


@functools.partial(jax.jit, static_argnames=("nv_pad",))
def _precise_mod_device(src, dst, w, comm, c_hi, c_lo, *, nv_pad):
    """Q = le*c - la2*c^2 in ds arithmetic over one shard's edge slab.

    ``src`` local indices, src-SORTED (CSR order; pad = nv_pad sorts last),
    ``dst`` indices into ``comm``'s id space, ``w`` zero on padding;
    ``comm`` the [nv_pad] assignment.  Vertex degrees are accumulated in ds
    from the slab itself, so even non-integral f32 weights keep f64-class
    totals end to end.
    """
    safe_src = jnp.minimum(src, nv_pad - 1)
    csrc = jnp.take(comm, safe_src)
    ck = jnp.take(comm, dst)
    internal = (csrc == ck) & (src < nv_pad)
    le = ds.ds_tree_sum(jnp.where(internal, w, jnp.zeros_like(w)))

    # per-vertex weighted degree (ds) from the src-sorted slab
    vd_hi, vd_lo, last = ds.ds_segment_sums_sorted(src, w)
    scat = jnp.where(last & (src < nv_pad), safe_src, nv_pad)
    deg_hi = jnp.zeros((nv_pad,), w.dtype).at[scat].set(vd_hi, mode="drop")
    deg_lo = jnp.zeros((nv_pad,), w.dtype).at[scat].set(vd_lo, mode="drop")

    # group by community, ds-pair segment sums, square, reduce
    cs, dh, dl = jax.lax.sort((comm, deg_hi, deg_lo), num_keys=1)
    run_hi, run_lo, _ = ds.ds_segment_sums_sorted(cs, dh, dl)
    sq_hi, sq_lo = ds.ds_mul((run_hi, run_lo), (run_hi, run_lo))
    la2 = ds.ds_tree_sum(sq_hi, sq_lo)

    c = (c_hi, c_lo)
    q = ds.ds_add(ds.ds_mul(le, c),
                  ds.ds_neg(ds.ds_mul(la2, ds.ds_mul(c, c))))
    return q[0], q[1]


def phase_modularity(dg, comm_pad: np.ndarray, device_slab=None,
                     tracer=None) -> float:
    """Precise modularity of ``comm_pad`` (padded-space labels) for the
    DistGraph's underlying graph, as a python float with f64-class accuracy.

    ``device_slab``: optional (src, dst, w) jax arrays ALREADY resident on
    device (single-shard layout) — the ds pass then runs on device with no
    O(E) upload.  Without it the host f64 pass is used; ``tracer`` then
    counts the edges the fused native pass summed
    (``evaluate_native_edges``).
    """
    g = dg.graph
    if device_slab is not None and dg.nshards == 1:
        src, dst, w = device_slab
        c_hi, c_lo = ds.ds_from_f64(1.0 / g.total_edge_weight_twice())
        q = _precise_mod_device(
            src, dst, w.astype(jnp.float32),
            jnp.asarray(np.asarray(comm_pad).astype(src.dtype)),
            c_hi.astype(jnp.float32), c_lo.astype(jnp.float32),
            nv_pad=dg.nv_pad,
        )
        return ds.ds_to_f64(q)
    # Assignment is on host at phase end.  One shard's old_to_pad is the
    # identity, so its labels are a view of the padded ones.
    comm_pad = np.asarray(comm_pad)
    comm_old = (comm_pad[:g.num_vertices] if dg.nshards == 1
                else comm_pad[dg.old_to_pad])
    if g.num_edges >= native.MIN_NATIVE_EDGES and native.available():
        nc = int(comm_old.max()) + 1
        e_in, two_m, a_c = native.modularity_terms(
            g.offsets, g.tails, g.weights, comm_old, nc)
        if tracer is not None:
            tracer.count("evaluate_native_edges", g.num_edges)
        # The oracle's own expression over the same (exact) terms.
        return float(e_in / two_m - np.square(a_c / two_m).sum())
    from cuvite_tpu.evaluate.modularity import modularity

    return modularity(g, comm_old)
