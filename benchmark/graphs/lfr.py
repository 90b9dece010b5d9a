"""LFR benchmark graphs (Lancichinetti, Fortunato & Radicchi, Phys. Rev. E
78, 046110, 2008): a copy of ``cuvite_tpu/workloads/synth.py::lfr_edges``,
draw for draw (``benchmark/tests/test_lfr.py`` holds them equal).

Degrees from the power law of exponent ``gamma`` on [k_min,
``max_degree``], k_min solved for ``mean_degree`` (drawn stratified);
community sizes from the power law of exponent ``beta`` on [``cmin``,
``cmax``], summing to ``n``; internal degree (1 - ``mu``) k, rounded at
random, each vertex in a community larger than it; configuration-model
pairing inside each community and across them, with self-loops,
multi-edges and external pairs inside one community re-paired or
rewired in bounded rounds and what is left dropped.
"""

from __future__ import annotations

import numpy as np

from benchmark.generators import _stream_base, splitmix64

_T_DEGREE = 0x10 << 56
_T_CSIZE = 0x11 << 56
_T_KIN = 0x12 << 56
_T_SLOT = 0x13 << 56
_T_PAIR_IN = 0x14 << 56
_T_PAIR_EX = 0x15 << 56
_T_SWAP_IN = 0x16 << 56
_T_SWAP_EX = 0x17 << 56
ROUNDS = 32


def _u01(x: np.ndarray) -> np.ndarray:
    return (x >> np.uint64(11)).astype(np.float64) * (1.0 / 9007199254740992.0)


def _hash_u01(tag: int, idx: np.ndarray, seed: int) -> np.ndarray:
    return _u01(splitmix64(_stream_base(tag, seed) + idx.astype(np.uint64)))


def _law(exponent: float, lo: int, hi: int, mean: float | None = None):
    """P(x) ~ x^-exponent on the integers [lo, hi] as (values, cdf); with
    ``mean``, the lowest value raised and its weight scaled to it."""
    xs = np.arange(lo, hi + 1, dtype=np.float64)
    w = np.power(xs, -float(exponent))
    if mean is not None:
        s0 = np.cumsum(w[::-1])[::-1]
        s1 = np.cumsum((xs * w)[::-1])[::-1]
        tail_mean = s1 / s0
        if not tail_mean[0] <= mean < hi:
            raise ValueError(f"no degree law on [{lo}, {hi}] has mean {mean}")
        i = int(np.searchsorted(tail_mean, mean, side="right")) - 1
        xs, w = xs[i:], w[i:].copy()
        w[0] = (mean * s0[i + 1] - s1[i + 1]) / (xs[0] - mean)
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    cdf[-1] = 1.0
    return xs.astype(np.int64), cdf


def _sizes(n: int, beta: float, cmin: int, cmax: int, seed: int):
    xs, cdf = _law(beta, cmin, cmax)
    u = _hash_u01(_T_CSIZE, np.arange(n // cmin + 1), seed)
    sizes = xs[np.searchsorted(cdf, u, side="right")]
    cum = np.cumsum(sizes)
    j = int(np.searchsorted(cum, n))
    sizes = sizes[:j + 1]
    sizes[j] = n - (cum[j - 1] if j else 0)
    if sizes[j] < cmin:
        rest = int(sizes[j])
        sizes = sizes[:j]
        room = np.flatnonzero(sizes < cmax)[:rest]
        if len(room) < rest:
            raise ValueError(f"{n} vertices do not split into communities "
                             f"of {cmin} to {cmax}")
        sizes[room] += 1
    return sizes


def _place(k_in: np.ndarray, sizes: np.ndarray, seed: int) -> np.ndarray:
    nv = len(k_in)
    slot_comm = np.repeat(np.arange(len(sizes)), sizes)
    order = np.argsort(splitmix64(_stream_base(_T_SLOT, seed)
                                  + np.arange(nv, dtype=np.uint64)),
                       kind="stable")
    slot_comm = slot_comm[order]
    slot_size = sizes[slot_comm]
    free = np.ones(nv, dtype=bool)
    comm_of = np.empty(nv, dtype=np.int64)
    by_kin = np.argsort(-k_in, kind="stable")
    runs = np.flatnonzero(np.r_[True, np.diff(k_in[by_kin]) != 0, True])
    for lo, hi in zip(runs[:-1], runs[1:]):
        verts = by_kin[lo:hi]
        slots = np.flatnonzero(free & (slot_size > k_in[verts[0]]))
        slots = slots[:len(verts)]
        if len(slots) < len(verts):
            raise ValueError(f"no community holds internal degree "
                             f"{int(k_in[verts[0]])}")
        comm_of[verts] = slot_comm[slots]
        free[slots] = False
    return comm_of


def _in_sorted(sorted_keys, keys):
    if not len(sorted_keys):
        return np.zeros(len(keys), dtype=bool)
    at = np.minimum(np.searchsorted(sorted_keys, keys), len(sorted_keys) - 1)
    return sorted_keys[at] == keys


def _insert_sorted(sorted_keys, keys):
    keys = np.sort(keys)
    return np.insert(sorted_keys, np.searchsorted(sorted_keys, keys), keys)


def _once(x):
    _, inv, counts = np.unique(x, return_inverse=True, return_counts=True)
    return counts[inv] == 1


def _pair(owner, group, bounds, comm_of, seed, pair_tag, swap_tag, cross):
    """The configuration model's rounds over vertices numbered by
    community: pair each group's pool in a seeded order, reject
    self-loops, repeats and (``cross``) pairs inside one community, and
    rewire each rejected pair (u, v) through an accepted edge (a, b) of
    its group into (u, a), (v, b).  Returns (lo, hi, dropped stubs)."""
    nv = len(comm_of)
    ngroups = len(bounds) - 1
    bits = np.uint64(max(ngroups.bit_length(), 1))
    acc = np.zeros(0, dtype=np.int64)
    pool_o, pool_g = owner, group
    for r in range(ROUNDS):
        if not len(pool_o):
            break
        h = splitmix64(_stream_base(pair_tag + (r << 48), seed)
                       + np.arange(len(pool_o), dtype=np.uint64))
        order = np.argsort((pool_g.astype(np.uint64) << (np.uint64(64) - bits))
                           | (h >> bits), kind="stable")
        o, g = pool_o[order], pool_g[order]
        m = len(o)
        starts = np.flatnonzero(np.r_[True, g[1:] != g[:-1]])
        pos = np.arange(m) - np.repeat(starts, np.diff(np.r_[starts, m]))
        head = np.flatnonzero((pos[:-1] % 2 == 0) & (g[1:] == g[:-1]))
        single = np.ones(m, dtype=bool)
        single[head] = False
        single[head + 1] = False
        u, v, pg = o[head], o[head + 1], g[head]
        key = np.minimum(u, v) * nv + np.maximum(u, v)
        bad = (u == v) | _in_sorted(acc, key)
        if cross:
            bad |= comm_of[u] == comm_of[v]
        good = np.flatnonzero(~bad)
        new_key, first = np.unique(key[good], return_index=True)
        ok = np.zeros(len(key), dtype=bool)
        ok[good[first]] = True
        acc = _insert_sorted(acc, new_key)
        u, v, pg = u[~ok], v[~ok], pg[~ok]
        left = np.ones(len(u), dtype=bool)
        if len(u) and len(acc):
            lo = np.searchsorted(acc, bounds[pg] * nv)
            span = np.searchsorted(acc, bounds[pg + 1] * nv) - lo
            hs = splitmix64(_stream_base(swap_tag + (r << 48), seed)
                            + np.arange(len(u), dtype=np.uint64))
            pick = np.minimum(
                lo + (hs % np.maximum(span, 1).astype(np.uint64)
                      ).astype(np.int64), len(acc) - 1)
            flip = (hs >> np.uint64(63)).astype(bool)
            ea, eb = acc[pick] // nv, acc[pick] % nv
            a, b = np.where(flip, eb, ea), np.where(flip, ea, eb)
            k1 = np.minimum(u, a) * nv + np.maximum(u, a)
            k2 = np.minimum(v, b) * nv + np.maximum(v, b)
            swap = ((span > 0) & (u != a) & (v != b) & (k1 != k2)
                    & ~_in_sorted(acc, k1) & ~_in_sorted(acc, k2))
            if cross:
                swap &= ((comm_of[u] != comm_of[a])
                         & (comm_of[v] != comm_of[b]))
            s = np.flatnonzero(swap)
            k12 = _once(np.concatenate([k1[s], k2[s]]))
            s = s[_once(pick[s]) & k12[:len(s)] & k12[len(s):]]
            acc = _insert_sorted(np.delete(acc, pick[s]),
                                 np.concatenate([k1[s], k2[s]]))
            left[s] = False
        pool_o = np.concatenate([u[left], v[left], o[single]])
        pool_g = np.concatenate([pg[left], pg[left], g[single]])
    return acc // nv, acc % nv, len(pool_o)


def edges(n: int, gamma: float, beta: float, mean_degree: float,
          max_degree: int, cmin: int, cmax: int, mu: float, seed: int):
    """(nv, src, dst): one record per undirected edge of the LFR graph."""
    n, max_degree, cmin, cmax = int(n), int(max_degree), int(cmin), int(cmax)
    seed = int(seed)
    if not 0.0 <= mu <= 1.0 or not 1 <= cmin <= cmax <= n \
            or max_degree >= n:
        raise ValueError("LFR parameters out of range")
    vidx = np.arange(n, dtype=np.int64)
    xs, cdf = _law(gamma, 1, max_degree, mean=mean_degree)
    h = splitmix64(_stream_base(_T_DEGREE, seed) + vidx.astype(np.uint64))
    rank = np.argsort(np.argsort(h, kind="stable"), kind="stable")
    k = xs[np.searchsorted(cdf, (rank + _u01(splitmix64(h))) / n,
                           side="right")]
    sizes = _sizes(n, beta, cmin, cmax, seed)
    k_in = np.floor((1.0 - mu) * k + _hash_u01(_T_KIN, vidx, seed)
                    ).astype(np.int64)
    comm_of = _place(k_in, sizes, seed)
    odd = np.bincount(comm_of, weights=k_in, minlength=len(sizes)) % 2 == 1
    cand = np.flatnonzero(odd[comm_of] & (k_in > 0))
    k_in[cand[np.unique(comm_of[cand], return_index=True)[1]]] -= 1
    by_comm = np.argsort(comm_of, kind="stable")
    comm_sorted = comm_of[by_comm]
    bounds = np.r_[0, np.cumsum(sizes)]
    k_in, k_ex = k_in[by_comm], (k - k_in)[by_comm]
    owner = np.repeat(vidx, k_in)
    s_in, d_in, _ = _pair(owner, comm_sorted[owner], bounds, comm_sorted,
                          seed, _T_PAIR_IN, _T_SWAP_IN, cross=False)
    owner = np.repeat(vidx, k_ex)
    s_ex, d_ex, _ = _pair(owner, np.zeros_like(owner), np.array([0, n]),
                          comm_sorted, seed, _T_PAIR_EX, _T_SWAP_EX,
                          cross=True)
    return (n, by_comm[np.concatenate([s_in, s_ex])],
            by_comm[np.concatenate([d_in, d_ex])])
