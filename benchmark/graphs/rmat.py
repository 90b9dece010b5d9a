"""Graph500 Kronecker/R-MAT edges: a copy of
``cuvite_tpu/io/generate.py::rmat_edges_numpy``, draw for draw, with its
id scramble (``benchmark/tests/test_generators.py`` holds them equal)."""

from __future__ import annotations

import numpy as np

from benchmark.generators import _MASK64, _splitmix64_inplace, splitmix64

def _scramble_ids(x: np.ndarray, bits: int, seed: int) -> np.ndarray:
    mask = np.uint64(_MASK64 if bits >= 64 else (1 << bits) - 1)
    s = np.uint64(seed)
    odd1 = splitmix64(s ^ np.uint64(0xA5A5A5A5)) | np.uint64(1)
    odd2 = splitmix64(s ^ np.uint64(0x5A5A5A5A)) | np.uint64(1)
    h = np.uint64(max(bits // 2, 1))
    x = np.asarray(x, dtype=np.uint64)
    with np.errstate(over="ignore"):
        x = (x * odd1) & mask
        x = x ^ (x >> h)
        x = (x * odd2) & mask
        x = x ^ (x >> h)
    return x & mask


def edges(scale: int, edge_factor: int, seed: int, a: float, b: float,
               c: float, chunk: int = 1 << 20):
    """(nv, src, dst): the draws of ``rmat_edges_numpy``.  Per edge
    e and level l the quadrant draws are splitmix64(seed + 2 e scale + 2l
    [+1]); ``u01(h) > t`` is taken as ``h >> 11 > floor(t 2^53)``, the same
    test on the integer, in chunks of edges that stay in cache."""
    nv = 1 << scale
    ne = edge_factor << scale
    ab = a + b

    def above(t):
        return np.uint64(int(np.floor(t * 9007199254740992.0)))

    t_ab, t_c, t_a = above(ab), above(c / (1.0 - ab)), above(a / ab)
    src = np.empty(ne, dtype=np.uint64)
    dst = np.empty(ne, dtype=np.uint64)
    one, eleven = np.uint64(1), np.uint64(11)
    for lo in range(0, ne, chunk):
        hi = min(ne, lo + chunk)
        base = (np.arange(lo, hi, dtype=np.uint64) * np.uint64(2 * scale)
                + np.uint64(seed))
        s = np.zeros(hi - lo, dtype=np.uint64)
        d = np.zeros(hi - lo, dtype=np.uint64)
        r1, r2, tmp = (np.empty_like(base) for _ in range(3))
        for level in range(scale):
            np.add(base, np.uint64(2 * level), out=r1)
            _splitmix64_inplace(r1, tmp)
            r1 >>= eleven
            np.add(base, np.uint64(2 * level + 1), out=r2)
            _splitmix64_inplace(r2, tmp)
            r2 >>= eleven
            sbit = r1 > t_ab
            s <<= one
            s |= sbit
            d <<= one
            d |= np.where(sbit, r2 > t_c, r2 > t_a)
        src[lo:hi] = s
        dst[lo:hi] = d
    src = _scramble_ids(src, scale, seed).astype(np.int64)
    dst = _scramble_ids(dst, scale, seed).astype(np.int64)
    return nv, src, dst
