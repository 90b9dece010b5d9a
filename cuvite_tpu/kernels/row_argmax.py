"""Pallas TPU kernel: neighbor-community dedup + modularity-gain argmax for
one degree bucket of the Louvain sweep.

Role: the narrow-degree classes of the per-vertex inner loop — the TPU
counterpart of the reference GPU's thread-per-vertex dedup/argmax kernels
(distGetMaxIndex, /root/reference/louvain_cuda.cu:1190-1346, and
computeMaxIndex, :641-876).  The XLA fallback (`_row_argmax` in
cuvite_tpu/louvain/bucketed.py) materializes the [rows, D] aggregation
intermediates in HBM; this kernel keeps the whole per-tile computation in
VMEM and writes only the per-row result vectors.

Layout: the bucket is TRANSPOSED to [D, N] so the lane dimension runs
across bucket rows (N = padded row count, a multiple of the 128-lane tile)
and the all-pairs dedup unrolls over the small static D in the sublane
dimension.  Per candidate slot j:

    wagg_j  = sum_k  w_k   where c_k == c_j          (duplicate aggregation)
    dup_j   = any_{k<j} c_k == c_j                   (j is not the leader)
    valid_j = !dup_j and c_j != curr
    gain_j  = 2*(wagg_j - eix) - 2*vdeg*(ay_j - ax)*const
                                   (louvain.cpp:2228 formula; ay pre-gathered)
    best    = running argmax over j, ties -> smaller community id
                                   (louvain.cpp:2230-2238 tie-break)

plus counter0 = sum of weights into the current community (incl. self
edges), which the caller turns into eix for the next stage.

SPMD: the kernel itself is shard-oblivious — the sharded bucketed step
(louvain/bucketed.py) calls it INSIDE its shard_map body on each shard's
[D, N] block.  The sparse ghost exchange additionally needs the SIZE of
the winning community for the singleton-swap guard; ``szT`` (the per-slot
attached community size, same layout as ``ayT``) switches the kernel to a
4-output form that tracks the winning slot's size through the running
argmax.  Every slot holding a community carries that community's size, so
the tracked value equals the XLA path's min-over-chosen-slots — bit-equal
by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
DEFAULT_TILE_N = 512
# Width above which the candidate loop switches from full unroll to
# lax.fori_loop (bounding compile time; identical arithmetic).  The
# unrolled form lets Mosaic schedule the small widths tightest.
UNROLL_MAX_WIDTH = 32
# Per-tile VMEM budget for the [D, T] operand blocks (c/w/ay (+size) +
# outputs), used to shrink the row tile for wide classes: the f32/int32
# blocks of D x tile_n must fit comfortably under ~16 MB v5e VMEM.
VMEM_BUDGET_BYTES = 6 << 20
# Scoped VMEM the compiler may give one kernel.  Mosaic's 16 MiB default
# is too tight for the widest class (D = 2048 at the 128-lane minimum
# tile needs ~17 MiB with its [D, T] loop temporaries); v5e has 128 MiB.
VMEM_LIMIT_BYTES = 64 << 20


def _kernel(const_ref, cT_ref, wT_ref, ayT_ref, curr_ref, vdeg_ref, sl_ref,
            ax_ref, *refs, sentinel: int, width: int, with_size: bool):
    if with_size:
        szT_ref, bc_ref, bg_ref, c0_ref, bs_ref = refs
    else:
        bc_ref, bg_ref, c0_ref = refs
        szT_ref = bs_ref = None
    c = cT_ref[:]          # [D, T] int32 neighbor communities
    w = wT_ref[:]          # [D, T] f32 edge weights
    ay = ayT_ref[:]        # [D, T] f32 comm_deg of each candidate
    sz = szT_ref[:] if with_size else None   # [D, T] int32 candidate size
    curr = curr_ref[:]     # [1, T] int32 current community
    vdeg = vdeg_ref[:]     # [1, T] f32 weighted degree k_i
    sl = sl_ref[:]         # [1, T] f32 self-loop weight of the vertex
    ax = ax_ref[:]         # [1, T] f32 comm_deg[curr] - k_i
    const = const_ref[0]   # f32 1/(2m)

    wdt = w.dtype
    is_cc = c == curr
    zero = jnp.zeros_like(w)
    c0 = jnp.sum(jnp.where(is_cc, w, zero), axis=0, keepdims=True)
    c0_ref[:] = c0
    # A vertex's weight into its current community comes entirely from its
    # own bucket row, so eix (counter0 minus self-loops) is row-local.
    eix = c0 - sl

    neg_inf = jnp.full(curr.shape, -jnp.inf, dtype=wdt)
    bg0 = neg_inf
    bc0 = jnp.full(curr.shape, sentinel, dtype=c.dtype)
    bs0 = jnp.full(curr.shape, sentinel, dtype=c.dtype) if with_size else None
    two_vdeg = 2.0 * vdeg

    def step_j(cj, ayj, szj, eq, dup_j, bc, bg, bs):
        """One candidate slot: aggregate duplicates, gain, running argmax.
        Shared by the unrolled (static j) and fori_loop (traced j) forms —
        identical arithmetic, so the two are bit-identical.  Operand order
        matches the XLA paths exactly (bucketed.py `_row_argmax`:
        ((2*vdeg)*(ay-ax))*const) so engines agree bit-for-bit even on
        non-dyadic constants where f32 association matters.  ``bs`` rides
        the same better/tie updates as ``bc``: any slot of the winning
        community carries the same attached size, so tracking the slot
        that wins the (gain, smaller-id) order IS the XLA min-over-chosen."""
        wagg_j = jnp.sum(jnp.where(eq, w, zero), axis=0, keepdims=True)
        valid_j = (~dup_j) & (cj != curr) if dup_j is not None \
            else (cj != curr)
        gain_j = 2.0 * (wagg_j - eix) - two_vdeg * (ayj - ax) * const
        gain_j = jnp.where(valid_j, gain_j, neg_inf)
        better = gain_j > bg
        tie = valid_j & (gain_j == bg)
        if bs is not None:
            take = better | (tie & (cj < bc))
            bs = jnp.where(take, szj, bs)
        bc = jnp.where(better, cj, jnp.where(tie, jnp.minimum(bc, cj), bc))
        bg = jnp.maximum(bg, gain_j)
        return bc, bg, bs

    if width <= UNROLL_MAX_WIDTH:
        bc, bg, bs = bc0, bg0, bs0
        for j in range(width):
            cj = c[j : j + 1, :]
            eq = c == cj
            dup_j = (jnp.any(eq[:j, :], axis=0, keepdims=True)
                     if j > 0 else None)
            szj = sz[j : j + 1, :] if with_size else None
            bc, bg, bs = step_j(cj, ay[j : j + 1, :], szj, eq, dup_j,
                                bc, bg, bs)
    else:
        # Wide classes: loop over candidate slots (compile time O(1) in
        # width).  Slot j's row is read from the REFS (a dynamic sublane
        # window, which Mosaic lowers) — a dynamic_slice of a loaded value
        # has no TPU lowering.  The duplicate-leader test uses a row-index
        # mask (rows k < j) on the full eq matrix.
        D, T = c.shape
        row_idx = jax.lax.broadcasted_iota(jnp.int32, (D, T), 0)

        def body(j, carry):
            bc, bg, bs = carry
            cj = cT_ref[pl.ds(j, 1), :]
            szj = szT_ref[pl.ds(j, 1), :] if with_size else None
            eq = c == cj
            dup_j = jnp.any(eq & (row_idx < j), axis=0, keepdims=True)
            return step_j(cj, ayT_ref[pl.ds(j, 1), :], szj, eq, dup_j,
                          bc, bg, bs)

        if with_size:
            bc, bg, bs = jax.lax.fori_loop(0, width, body, (bc0, bg0, bs0))
        else:
            bc, bg = jax.lax.fori_loop(
                0, width, lambda j, cr: body(j, cr + (None,))[:2],
                (bc0, bg0))
            bs = None
    bc_ref[:] = bc
    bg_ref[:] = bg
    if with_size:
        bs_ref[:] = bs


@functools.partial(
    jax.jit,
    static_argnames=("sentinel", "tile_n", "interpret"),
)
def row_argmax_pallas(cT, wT, ayT, curr, vdeg, sl, ax, constant, *,
                      szT=None, sentinel: int, tile_n: int = DEFAULT_TILE_N,
                      interpret: bool = False):
    """Run the bucket kernel.

    cT/wT/ayT: [D, N] transposed bucket matrices; curr/vdeg/sl/ax: [N]
    (sl = per-vertex self-loop weight); constant: scalar.  N must be a
    multiple of the row tile (bucket row counts are padded to powers of
    two >= 128 by the runner for this path).  The tile shrinks below
    ``tile_n`` for wide D so the [D, tile] operand blocks stay inside
    the VMEM budget.  Returns (best_c [N] int, best_gain [N],
    counter0 [N]); with ``szT`` (the [D, N] attached community-size
    matrix of the sparse exchange) additionally best_size [N] int.
    """
    D, N = cT.shape
    with_size = szT is not None
    n_mats = 4 if with_size else 3
    tile = min(tile_n, N)
    # Wide classes: bound n_mats * D * tile * 4B by the VMEM budget (pow2
    # shrink keeps N % tile == 0 — both are powers of two >= 128).
    while tile > LANE and n_mats * D * tile * 4 > VMEM_BUDGET_BYTES:
        tile //= 2
    assert N % tile == 0 and tile % LANE == 0, (N, tile)
    grid = (N // tile,)

    mat_spec = pl.BlockSpec((D, tile), lambda i: (0, i),
                            memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, tile), lambda i: (0, i),
                            memory_space=pltpu.VMEM)
    out_shapes = (
        jax.ShapeDtypeStruct((1, N), cT.dtype),
        jax.ShapeDtypeStruct((1, N), wT.dtype),
        jax.ShapeDtypeStruct((1, N), wT.dtype),
    )
    out_specs = (vec_spec, vec_spec, vec_spec)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        mat_spec, mat_spec, mat_spec,
        vec_spec, vec_spec, vec_spec, vec_spec,
    ]
    operands = [
        jnp.reshape(constant, (1,)).astype(wT.dtype),
        cT, wT, ayT,
        curr.reshape(1, N), vdeg.reshape(1, N), sl.reshape(1, N),
        ax.reshape(1, N),
    ]
    if with_size:
        in_specs.append(mat_spec)
        operands.append(szT)
        out_shapes = out_shapes + (jax.ShapeDtypeStruct((1, N), cT.dtype),)
        out_specs = out_specs + (vec_spec,)
    kernel = functools.partial(_kernel, sentinel=sentinel, width=D,
                               with_size=with_size)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands)
    if with_size:
        bc, bg, c0, bs = out
        return bc.reshape(N), bg.reshape(N), c0.reshape(N), bs.reshape(N)
    bc, bg, c0 = out
    return bc.reshape(N), bg.reshape(N), c0.reshape(N)
