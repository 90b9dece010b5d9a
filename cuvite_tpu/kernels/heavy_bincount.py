"""Pallas TPU kernel: community-range-tile bincount dedup + gain argmax
for the HEAVY degree class (> 8192 neighbors per vertex).

Role: the TPU counterpart of the reference GPU's huge-class kernel, which
bincounts neighbor weights into a 20M-entry dense per-block scratch
indexed by dense community id (distGetMaxIndex_large_new,
/root/reference/louvain_cuda.cu:878-1022).  An O(nv) dense scratch cannot
live in VMEM (~16 MB on v5e), so this kernel tiles the COMMUNITY RANGE
(tools/heavy_kernel_design.md): for each tile [t*C, (t+1)*C) it
accumulates the row's weights into the tile's [C] bins by comparing
each neighbor community with the tile's candidates — duplicate
aggregation IS the bincount — and carries a running (best_gain, best_c)
across tiles.

Layout: transposed [D, H] rows (H = heavy vertices, D = max heavy
degree, rows padded with c = pad id >= n_tiles*C and w = 0).  The grid
is (hub tile, community tile, neighbor chunk): 128 hubs ride the lanes,
each step reads a lane-aligned [Dc, 128] block and walks its rows
through the refs (``pl.ds``), accumulating a [C, 128] bincount in VMEM
scratch across chunks; `comm_deg` (the ay gather of the narrow kernel)
arrives as a contiguous [1, C] block per community tile — a
community-RANGE tile needs no gather at all.

Tie-break matches the narrow kernel (`row_argmax.py`) and the reference
(`louvain.cpp:2230-2238`): max gain, ties -> smaller community id.  Tiles
ascend in community id, so a strict `>` merge keeps the earlier (smaller)
id on cross-tile ties, and the in-tile rule picks the smallest candidate
among equal gains.

Status (PR 21): OPT-IN (CUVITE_HEAVY_KERNEL=1; interpret mode off the
TPU, which is how tier-1 pins its parity with the sorted path).  It
compiles for the v5e, but its cost grows with D x nv_ceil per 128-hub
tile: on the chip, at the golden graph's phase-0 hub geometry (D =
10240, 128 hubs, nv_ceil = 2^23) one call took 15.5 s against 23.3 ms
for the sorted XLA path on the same rows (tools/heavy_ab.py; PERF.md).
The sorted path is the default on every backend.  The [D, H] layout is
built per phase by ``build_heavy_layout``; layouts over its element
budget (CUVITE_HEAVY_ELEMS), the sparse exchange and meshes keep the
sorted path.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
DEFAULT_C_TILE = 512     # communities per tile ([Dc, C] one-hot block)
DEFAULT_D_CHUNK = 1024   # neighbor slots reduced per fori step
# [Dc, C] f32 one-hot + eq intermediates must sit well under v5e VMEM.
assert DEFAULT_C_TILE * DEFAULT_D_CHUNK * 4 <= (4 << 20)

# [D, Hp] layout element budget: the transposed heavy rows live in HBM
# for the whole phase (two arrays, id + weight), so a hub set whose
# padded matrix exceeds this stays on the sorted path instead of
# doubling the slab's footprint.  2^24 slots = 64 MiB per f32 array.
DEFAULT_MAX_LAYOUT_ELEMS = 1 << 24


def heavy_kernel_enabled() -> bool:
    """Opt-in policy for the heavy (> 8192 neighbors) degree class:
    CUVITE_HEAVY_KERNEL=1 routes the heavy residual through the
    community-range-tile kernel (interpret mode off the TPU — how tier-1
    runs the full driver to pin parity).  Off by default: the sorted XLA
    path is 664x faster per call on the chip at real hub geometry (module
    docstring).  Read per PhaseRunner construction, not at import."""
    v = os.environ.get("CUVITE_HEAVY_KERNEL", "").strip().lower()
    return v in ("1", "true", "on")


def _layout_budget() -> int:
    from cuvite_tpu.utils.envknob import env_int

    return env_int("CUVITE_HEAVY_ELEMS", DEFAULT_MAX_LAYOUT_ELEMS)


def build_heavy_layout(heavy_src, heavy_dst, heavy_w, *, nv_local: int,
                       pad_id: int, d_chunk: int = DEFAULT_D_CHUNK,
                       max_elems: int | None = None):
    """Phase-static [D, Hp] transposed row layout of the heavy residual,
    from the BucketPlan's padded (src, dst, w) triples.

    Returns ``(verts [Hp], dstT [D, Hp], wT [D, Hp])`` — one hub per
    column, columns in ascending vertex id, D a multiple of ``d_chunk``,
    Hp a pow2 >= 8 (stable shapes: phases whose hub geometry pads to the
    same (D, Hp) reuse the compiled step).  Padding slots carry dst ==
    ``pad_id`` (the step masks them to a community >= nv_ceil, so they
    are never candidates) and w == 0; padding columns carry verts ==
    nv_local (dropped at assembly).  Returns None — the caller keeps the
    sorted path, with a coverage warning — when there are no heavy
    edges or the padded layout exceeds ``max_elems``
    (CUVITE_HEAVY_ELEMS; the PALLAS_MAX_WIDTH degrade pattern).
    """
    if max_elems is None:
        max_elems = _layout_budget()
    hs = np.asarray(heavy_src)
    real = hs < nv_local
    s = hs[real].astype(np.int64)
    if len(s) == 0:
        return None
    d = np.asarray(heavy_dst)[real]
    w = np.asarray(heavy_w)[real]
    if len(s) > 1 and np.any(s[:-1] > s[1:]):
        # Plan triples arrive CSR-ordered; color-masked or synthetic
        # inputs may not be.  Stable, so within-row edge order (the f32
        # accumulation order contract) is preserved.
        order = np.argsort(s, kind="stable")
        s, d, w = s[order], d[order], w[order]
    verts, counts = np.unique(s, return_counts=True)
    H = len(verts)
    Hp = max(1 << int(H - 1).bit_length() if H > 1 else 1, 8)
    D = int(-(-int(counts.max()) // d_chunk)) * d_chunk
    if D * Hp > max_elems:
        return None
    row_start = np.searchsorted(s, verts)
    rows = np.arange(D, dtype=np.int64)
    idx = row_start[None, :] + rows[:, None]        # [D, H]
    has = rows[:, None] < counts[None, :]
    idx = np.minimum(idx, len(d) - 1)
    dstT = np.full((D, Hp), pad_id, dtype=np.asarray(heavy_dst).dtype)
    wT = np.zeros((D, Hp), dtype=w.dtype)
    dstT[:, :H] = np.where(has, d[idx], pad_id)
    wT[:, :H] = np.where(has, w[idx], 0)
    verts_out = np.full(Hp, nv_local, dtype=np.int64)
    verts_out[:H] = verts
    return verts_out, dstT, wT


def _kernel(const_ref, cT_ref, wT_ref, ay_ref, curr_ref, vdeg_ref, sl_ref,
            ax_ref, bc_ref, bg_ref, c0_ref, wagg_ref, cnt_ref, *,
            c_tile: int, d_chunk: int):
    # Grid (hub tile r, community tile t, neighbor chunk k), k fastest.
    # Blocks: cT/wT [Dc, L] (L = 128 hubs on the lanes), ay [1, C]
    # (lane-dense; transposed in-kernel to the [C, 1] candidate column —
    # an [nv_ceil, 1] operand would pad every community to 128 lanes in
    # HBM), per-hub vectors and outputs [1, L].  The outputs stay resident across (t, k) as the running
    # (best_gain, best_c) and counter0 accumulators; the [C, L] bincount
    # of one community tile accumulates in VMEM scratch across k.
    t = pl.program_id(1)
    k = pl.program_id(2)
    wdt = wagg_ref.dtype
    idt = bc_ref.dtype
    curr = curr_ref[...]                        # [1, L]
    big = jnp.iinfo(idt).max

    @pl.when((t == 0) & (k == 0))
    def _init():
        c0_ref[...] = jnp.zeros_like(c0_ref)
        bg_ref[...] = jnp.full(bg_ref.shape, -jnp.inf, dtype=wdt)
        bc_ref[...] = jnp.full(bc_ref.shape, big, dtype=idt)

    @pl.when(t == 0)
    def _counter0():
        # counter0 (weight into the current community, incl. self edges)
        # is row-local: summed once, on the first community tile.
        c0_ref[...] += jnp.sum(
            jnp.where(cT_ref[...] == curr, wT_ref[...], 0.0), axis=0,
            keepdims=True)

    @pl.when(k == 0)
    def _zero():
        wagg_ref[...] = jnp.zeros_like(wagg_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    cand = t * c_tile + jax.lax.broadcasted_iota(jnp.int32, (c_tile, 1), 0)

    def row(d, carry):
        # One neighbor slot of every hub in the tile: its community row
        # [1, L] against the tile's candidates [C, 1].  Presence COUNT,
        # not weight: zero-weight edges are candidates exactly as in the
        # XLA paths (bucketed.py `_row_argmax` — 'No w>0 filter').
        # Padding slots carry c >= n_tiles*c_tile, so eq never matches.
        eq = cT_ref[pl.ds(d, 1), :] == cand       # [C, L]
        wagg_ref[...] += jnp.where(eq, wT_ref[pl.ds(d, 1), :], 0.0)
        cnt_ref[...] += eq.astype(wdt)
        return carry

    jax.lax.fori_loop(0, d_chunk, row, 0)

    @pl.when(k == pl.num_programs(2) - 1)
    def _argmax():
        eix = c0_ref[...] - sl_ref[...]
        # [1, C] -> [C, 1] via an (8, C) -> (C, 8) transpose, the aligned
        # shape Mosaic transposes natively.
        ay = jnp.transpose(jnp.broadcast_to(ay_ref[...], (8, c_tile)))
        ay = ay[:, :1]
        valid = (cnt_ref[...] > 0) & (cand != curr)
        # Operand order matches the XLA paths exactly (bucketed.py
        # `_row_argmax`): 2*(wagg-eix) - ((2*vdeg)*(ay-ax))*const.
        gain = (2.0 * (wagg_ref[...] - eix)
                - 2.0 * vdeg_ref[...] * (ay - ax_ref[...])
                * const_ref[0])
        gain = jnp.where(valid, gain, -jnp.inf)
        tile_bg = jnp.max(gain, axis=0, keepdims=True)          # [1, L]
        tile_bc = jnp.min(jnp.where(gain == tile_bg, cand, big), axis=0,
                          keepdims=True).astype(idt)
        better = tile_bg > bg_ref[...]            # strict: earlier tile
        bc_ref[...] = jnp.where(better, tile_bc, bc_ref[...])
        bg_ref[...] = jnp.where(better, tile_bg, bg_ref[...])


@functools.partial(
    jax.jit,
    static_argnames=("c_tile", "d_chunk", "interpret"),
)
def heavy_argmax_pallas(cT, wT, comm_deg, curr, vdeg, sl, ax, constant, *,
                        c_tile: int = DEFAULT_C_TILE,
                        d_chunk: int = DEFAULT_D_CHUNK,
                        interpret: bool = False):
    """Run the heavy-class tile kernel.

    cT/wT: [D, H] transposed heavy rows (one vertex per column; D a
    multiple of ``d_chunk``; padding slots carry c >= n_tiles*c_tile and
    w = 0).  comm_deg: [nv_ceil] community weighted degrees, nv_ceil a
    multiple of ``c_tile`` (pad with zeros).  curr/vdeg/sl/ax: [H] per
    vertex (sl = self-loop weight, ax = comm_deg[curr] - k_i).  Returns
    (best_c [H] int, best_gain [H], counter0 [H]); best_c is the int-max
    sentinel where no valid move exists (caller keeps such vertices in
    place, same contract as the narrow kernel).
    """
    D, H = cT.shape
    (nv_ceil,) = comm_deg.shape
    assert D % d_chunk == 0, (D, d_chunk)
    assert nv_ceil % c_tile == 0, (nv_ceil, c_tile)
    # Hubs ride the 128 lanes: pad H to a lane multiple with all-padding
    # columns (c never a candidate, w = 0), dropped from the outputs.
    hp = -(-H // LANE) * LANE
    cT = jnp.pad(cT, ((0, 0), (0, hp - H)), constant_values=nv_ceil)
    wT = jnp.pad(wT, ((0, 0), (0, hp - H)))
    vecs = [jnp.pad(v, (0, hp - H)).reshape(1, hp)
            for v in (curr, vdeg, sl, ax)]
    grid = (hp // LANE, nv_ceil // c_tile, D // d_chunk)

    row_spec = pl.BlockSpec((d_chunk, LANE), lambda r, t, k: (k, r),
                            memory_space=pltpu.VMEM)
    ay_spec = pl.BlockSpec((1, c_tile), lambda r, t, k: (0, t),
                           memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, LANE), lambda r, t, k: (0, r),
                            memory_space=pltpu.VMEM)
    out_shapes = (
        jax.ShapeDtypeStruct((1, hp), cT.dtype),
        jax.ShapeDtypeStruct((1, hp), wT.dtype),
        jax.ShapeDtypeStruct((1, hp), wT.dtype),
    )
    kernel = functools.partial(_kernel, c_tile=c_tile, d_chunk=d_chunk)
    bc, bg, c0 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            row_spec, row_spec, ay_spec,
            vec_spec, vec_spec, vec_spec, vec_spec,
        ],
        out_specs=(vec_spec, vec_spec, vec_spec),
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((c_tile, LANE), wT.dtype),
                        pltpu.VMEM((c_tile, LANE), wT.dtype)],
        interpret=interpret,
    )(
        jnp.reshape(constant, (1,)).astype(wT.dtype),
        cT, wT, comm_deg.reshape(1, nv_ceil), *vecs,
    )
    return bc[0, :H], bg[0, :H], c0[0, :H]
