"""Offline workload synthesizer: graphs with planted communities,
emitted straight to a Vite file at a requested edge count.

The registry's datasets (com-Orkut / Friendster / uk-2007) need the
network; this generator is the offline fallback that keeps the rig from
ever blocking on it (VERDICT r5 missing #5 explicitly allows "generate a
Vite-format file from a published degree sequence and say so").  It is
fully deterministic — every random draw is a counter-based splitmix64
hash of (seed, index), the same scheme io/generate.py uses for R-MAT —
so a (edges, seed, profile) triple always produces byte-identical output
(the conversion pipeline canonicalizes row order), and golden envelopes
over synthesized graphs are meaningful.

Two profiles:

``lfr`` is the LFR benchmark construction (Lancichinetti, Fortunato &
Radicchi, Phys. Rev. E 78, 046110, 2008; :func:`lfr_edges`): power-law
degrees on [k_min, k_max] with the mean given, power-law community sizes
on [cmin, cmax] summing to N, each vertex's internal degree (1 - mu) k
in a community larger than it, configuration-model pairing inside and
across communities, and no self-loop or multi-edge.

``powerlaw`` is the older stand-in that borrows the LFR ingredients but
is not the construction (the serving, stream and sub-row tests use it):
  * vertex degree draws  d_i ~ dmin * u^(-1/(alpha-1)), capped at 4
    sqrt(N), scaled exactly to the requested total;
  * community sizes from a second power law; vertices assigned to
    contiguous ranges; a deterministic ``overlap`` fraction of vertices
    holds a second membership (their edges split between the two);
  * each draw is intra-community with probability 1-mu (uniform member
    of one of the vertex's communities), else a uniform global target;
    self-draws are dropped, parallel edges kept (multigraph-legal), so
    mu holds in expectation only.

Ground truth (primary membership, LFR ``vertex community`` 1-based
format — evaluate.compare.load_ground_truth reads it) goes to
``<out>.truth``; full provenance, including the output file's sha256,
to ``<out>.provenance.json``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time

import numpy as np

from cuvite_tpu.utils.rng import splitmix64, u01
from cuvite_tpu.workloads.convert import DEFAULT_CHUNK_EDGES, edges_to_vite

PROFILES = ("powerlaw", "lfr")

# Stream tags: every hash stream is splitmix64(seed * STRIDE + tag + index)
# with a distinct tag so streams never collide across uses.
_T_DEGREE = 0x01 << 56
_T_CSIZE = 0x02 << 56
_T_OVERLAP = 0x03 << 56
_T_ALT = 0x04 << 56
_T_MIX = 0x05 << 56
_T_PICK = 0x06 << 56
_T_INTRA = 0x07 << 56
_T_INTER = 0x08 << 56
_T_MANY = 0x09 << 56
# Churn streams (ISSUE 17): delete ranks, insert endpoints, insert
# weights — distinct tags so a churn stream never collides with the
# base synthesis draws of the same seed.
_T_CHURN_DEL = 0x0A << 56
_T_CHURN_INS = 0x0B << 56
_T_CHURN_W = 0x0C << 56
# LFR streams (0x0D and 0x0E are the benchmark's vertex scramble and
# edge-list order): degrees, community sizes, internal-degree rounding,
# community slots, and per pairing round (index bits 48-55) the stub
# order and the rewiring picks, internal and external apart.
_T_LFR_DEGREE = 0x10 << 56
_T_LFR_CSIZE = 0x11 << 56
_T_LFR_KIN = 0x12 << 56
_T_LFR_SLOT = 0x13 << 56
_T_LFR_PAIR_IN = 0x14 << 56
_T_LFR_PAIR_EX = 0x15 << 56
_T_LFR_SWAP_IN = 0x16 << 56
_T_LFR_SWAP_EX = 0x17 << 56
_STRIDE = 0x9E3779B97F4A7C15
_MASK64 = (1 << 64) - 1
# Pairing rounds of the LFR configuration model.
LFR_ROUNDS = 32


def _stream_base(tag: int, seed: int) -> np.uint64:
    """Per-(seed, tag) stream offset; the multiply wraps mod 2^64 by
    design (computed in Python ints so numpy stays warning-free)."""
    return np.uint64((seed * _STRIDE + tag) & _MASK64)


def _hash_u01(tag: int, idx: np.ndarray, seed: int) -> np.ndarray:
    return u01(splitmix64(_stream_base(tag, seed) + idx.astype(np.uint64)))


def _exact_counts(weights: np.ndarray, total: int) -> np.ndarray:
    """Integer counts proportional to ``weights`` summing to exactly
    ``total`` (cumulative rounding: deterministic, order-stable)."""
    cum = np.cumsum(weights, dtype=np.float64)
    cum *= total / cum[-1]
    bounds = np.floor(cum + 0.5).astype(np.int64)
    counts = np.diff(np.concatenate([[0], bounds]))
    counts[-1] += total - bounds[-1]
    return counts


@dataclasses.dataclass
class SynthSpec:
    """Resolved synthesizer parameters (recorded in provenance)."""

    profile: str
    edges: int           # target directed records in the Vite file
    seed: int
    alpha: float         # degree power-law exponent
    mu: float            # inter-community mixing fraction
    dmin: int
    edge_factor: int     # mean directed degree -> nv = edges / edge_factor
    comm_min: int
    comm_beta: float     # community-size power-law exponent
    overlap: float       # fraction of vertices with a second membership
    bits64: bool

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _community_layout(nv: int, spec: SynthSpec):
    """Community sizes from a power law covering exactly nv vertices.
    Returns (bounds[nc+1], sizes[nc])."""
    cmax = max(spec.comm_min + 1, nv // 16 or 1)
    sizes = []
    covered = 0
    batch = 0
    while covered < nv:
        idx = np.arange(batch * 4096, (batch + 1) * 4096, dtype=np.int64)
        u = _hash_u01(_T_CSIZE, idx, spec.seed)
        s = np.minimum(
            (spec.comm_min * np.power(1.0 - u, -1.0 / (spec.comm_beta - 1.0))
             ).astype(np.int64), cmax)
        sizes.append(s)
        covered += int(s.sum())
        batch += 1
    sizes = np.concatenate(sizes)
    cut = int(np.searchsorted(np.cumsum(sizes), nv, side="left")) + 1
    sizes = sizes[:cut]
    sizes[-1] -= int(sizes.sum()) - nv  # trim the last community to fit
    if sizes[-1] <= 0:  # merge a degenerate tail into its neighbor
        sizes = sizes[:-1]
        sizes[-1] += nv - int(sizes.sum())
    bounds = np.zeros(len(sizes) + 1, dtype=np.int64)
    np.cumsum(sizes, out=bounds[1:])
    return bounds, sizes


def _edge_chunk_stream(nv: int, draws: np.ndarray, bounds: np.ndarray,
                       spec: SynthSpec, chunk_edges: int):
    """Yield (src, dst, None) chunks; every value is a pure hash of its
    global draw index, so chunking never changes the edge set."""
    nc = len(bounds) - 1
    comm_of = np.empty(nv, dtype=np.int64)
    for c in range(nc):
        comm_of[bounds[c]:bounds[c + 1]] = c
    # Second membership for a deterministic `overlap` fraction.
    vidx = np.arange(nv, dtype=np.int64)
    has_alt = _hash_u01(_T_OVERLAP, vidx, spec.seed) < spec.overlap
    alt_pick = splitmix64(_stream_base(_T_ALT, spec.seed)
                          + vidx.astype(np.uint64))
    alt_of = ((comm_of + 1 + (alt_pick % np.uint64(max(nc - 1, 1)))
               .astype(np.int64)) % nc) if nc > 1 else comm_of.copy()
    alt_of = np.where(has_alt, alt_of, comm_of)

    cum = np.zeros(nv + 1, dtype=np.int64)
    np.cumsum(draws, out=cum[1:])
    lo_v = 0
    while lo_v < nv:
        hi_v = int(np.searchsorted(cum, cum[lo_v] + chunk_edges,
                                   side="left"))
        hi_v = min(max(hi_v, lo_v + 1), nv)
        src = np.repeat(np.arange(lo_v, hi_v, dtype=np.int64),
                        draws[lo_v:hi_v])
        if not len(src):
            lo_v = hi_v
            continue
        gidx = np.arange(int(cum[lo_v]), int(cum[hi_v]), dtype=np.int64)
        intra = _hash_u01(_T_MIX, gidx, spec.seed) >= spec.mu
        use_alt = _hash_u01(_T_PICK, gidx, spec.seed) < 0.5
        comm = np.where(use_alt, alt_of[src], comm_of[src])
        clo = bounds[comm]
        csz = (bounds[comm + 1] - clo).astype(np.uint64)
        h_in = splitmix64(_stream_base(_T_INTRA, spec.seed)
                          + gidx.astype(np.uint64))
        t_in = clo + (h_in % np.maximum(csz, 1)).astype(np.int64)
        h_out = splitmix64(_stream_base(_T_INTER, spec.seed)
                           + gidx.astype(np.uint64))
        t_out = (h_out % np.uint64(nv)).astype(np.int64)
        dst = np.where(intra, t_in, t_out)
        keep = src != dst
        yield src[keep], dst[keep], None
        lo_v = hi_v


def _sha256_file(path: str, block: int = 8 << 20) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            buf = f.read(block)
            if not buf:
                break
            h.update(buf)
    return h.hexdigest()


def write_provenance(out_path: str, payload: dict) -> str:
    path = out_path + ".provenance.json"
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def many_seed(seed: int, index: int) -> int:
    """Per-graph seed of a ``--many`` set: splitmix64 of (seed, index)
    on its own stream tag, so graph k is deterministic, independent of
    the set size K, and never collides with the base generator's
    streams (two members of one set share no draw)."""
    return int(splitmix64(_stream_base(_T_MANY, seed)
                          + np.uint64(index))) & ((1 << 62) - 1)


def _layout(edges: int, spec: SynthSpec, seed: int):
    """Shared degree/community layout of one synthesized graph."""
    n_pairs = edges // 2
    nv = max(64, edges // spec.edge_factor)
    dmax = max(spec.dmin * 4, int(np.sqrt(nv) * 4))
    vidx = np.arange(nv, dtype=np.int64)
    u = _hash_u01(_T_DEGREE, vidx, seed)
    wdeg = spec.dmin * np.power(1.0 - u, -1.0 / (spec.alpha - 1.0))
    wdeg = np.minimum(wdeg, dmax)
    draws = _exact_counts(wdeg, n_pairs)
    bounds, sizes = _community_layout(nv, spec)
    return nv, draws, bounds, sizes


def _powerlaw_law(exponent: float, lo: int, hi: int,
                  mean: float | None = None):
    """The integer law P(x) ~ x^-exponent on [lo, hi], as (values, cdf).
    With ``mean``, lo is raised to the largest integer whose law has a
    mean of at most ``mean``, and that lowest value's weight scaled down
    so the law's mean is ``mean`` exactly: LFR's k_min, solved for the
    mean degree."""
    xs = np.arange(lo, hi + 1, dtype=np.float64)
    w = np.power(xs, -float(exponent))
    if mean is not None:
        s0 = np.cumsum(w[::-1])[::-1]
        s1 = np.cumsum((xs * w)[::-1])[::-1]
        tail_mean = s1 / s0  # the law's mean on [x, hi], rising with x
        if not tail_mean[0] <= mean < hi:
            raise ValueError(f"no degree law on [{lo}, {hi}] with exponent "
                             f"{exponent} has mean {mean}")
        i = int(np.searchsorted(tail_mean, mean, side="right")) - 1
        xs, w = xs[i:], w[i:].copy()
        w[0] = (mean * s0[i + 1] - s1[i + 1]) / (xs[0] - mean)
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    cdf[-1] = 1.0
    return xs.astype(np.int64), cdf


def _lfr_community_sizes(n: int, beta: float, cmin: int, cmax: int,
                         seed: int) -> np.ndarray:
    """Community sizes drawn from the power law on [cmin, cmax] until
    they cover n; the last one is cut to fit, and a remainder below cmin
    goes one vertex each to the first communities below cmax."""
    xs, cdf = _powerlaw_law(beta, cmin, cmax)
    u = _hash_u01(_T_LFR_CSIZE, np.arange(n // cmin + 1), seed)
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


def _lfr_place(k_in: np.ndarray, sizes: np.ndarray, seed: int) -> np.ndarray:
    """Each vertex's community, one larger than its internal degree.
    Vertices go in order of falling internal degree (ties by id), each
    to the next free slot, in a seeded order of all slots, of a community
    large enough: uniform among the free slots that can hold it.  Placing
    the largest first, it fails only where no placement exists."""
    nv = len(k_in)
    slot_comm = np.repeat(np.arange(len(sizes)), sizes)
    order = np.argsort(splitmix64(_stream_base(_T_LFR_SLOT, int(seed))
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
            raise ValueError(f"no community of at most {int(sizes.max())} "
                             f"vertices holds internal degree "
                             f"{int(k_in[verts[0]])}")
        comm_of[verts] = slot_comm[slots]
        free[slots] = False
    return comm_of


def _in_sorted(sorted_keys: np.ndarray, keys: np.ndarray) -> np.ndarray:
    if not len(sorted_keys):
        return np.zeros(len(keys), dtype=bool)
    at = np.minimum(np.searchsorted(sorted_keys, keys), len(sorted_keys) - 1)
    return sorted_keys[at] == keys


def _insert_sorted(sorted_keys: np.ndarray, keys: np.ndarray) -> np.ndarray:
    keys = np.sort(keys)
    return np.insert(sorted_keys, np.searchsorted(sorted_keys, keys), keys)


def _once(x: np.ndarray) -> np.ndarray:
    """Where each value of ``x`` occurs exactly once."""
    _, inv, counts = np.unique(x, return_inverse=True, return_counts=True)
    return counts[inv] == 1


def _lfr_pair(owner: np.ndarray, group: np.ndarray, bounds: np.ndarray,
              comm_of: np.ndarray, seed: int, pair_tag: int, swap_tag: int,
              cross: bool):
    """Configuration model over vertices numbered by community: group g
    is the vertices [bounds[g], bounds[g + 1]), so its pairs' keys lo *
    nv + hi form one run of the sorted accepted keys.  Each round puts
    the pool of stubs (``owner`` vertices, their ``group``) in a seeded
    order per group and pairs them two by two.  A pair is rejected if it
    is a self-loop, repeats an accepted pair or one earlier in its round,
    or (``cross``) lies inside one community.  A rejected pair (u, v)
    then tries LFR's rewiring: an accepted edge (a, b) of its group, at
    random, becomes (u, a) and (v, b) where both are new and allowed,
    each edge and each new pair used once in the round.  What fails
    returns to the pool; what ``LFR_ROUNDS`` rounds leave is dropped.
    Returns (lo, hi, dropped stubs)."""
    nv = len(comm_of)
    ngroups = len(bounds) - 1
    bits = np.uint64(max(ngroups.bit_length(), 1))
    acc = np.zeros(0, dtype=np.int64)
    pool_o, pool_g = owner, group
    for r in range(LFR_ROUNDS):
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
            # Rewire each rejected pair through an accepted edge of its
            # group, taken either way round.
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


def _lfr(n: int, gamma: float, beta: float, mean_degree: float,
         max_degree: int, cmin: int, cmax: int, mu: float, seed: int):
    """:func:`lfr_edges`, and the count of stubs it dropped."""
    n, max_degree, cmin, cmax = int(n), int(max_degree), int(cmin), int(cmax)
    seed = int(seed)
    if not 0.0 <= mu <= 1.0:
        raise ValueError(f"mixing mu={mu} is not in [0, 1]")
    if not 1 <= cmin <= cmax <= n:
        raise ValueError(f"community sizes [{cmin}, {cmax}] do not fit "
                         f"{n} vertices")
    if max_degree >= n:
        raise ValueError(f"max degree {max_degree} needs more than {n} "
                         "vertices")
    vidx = np.arange(n, dtype=np.int64)
    # Degrees by stratified draws: vertex v takes the quantile (rank_v +
    # u_v) / n, rank a seeded permutation, so each draw is uniform and
    # the n of them fill the law's quantiles evenly (the mean holds).
    xs, cdf = _powerlaw_law(gamma, 1, max_degree, mean=mean_degree)
    h = splitmix64(_stream_base(_T_LFR_DEGREE, seed)
                   + vidx.astype(np.uint64))
    rank = np.argsort(np.argsort(h, kind="stable"), kind="stable")
    k = xs[np.searchsorted(cdf, (rank + u01(splitmix64(h))) / n,
                           side="right")]
    sizes = _lfr_community_sizes(n, beta, cmin, cmax, seed)
    k_in = np.floor((1.0 - mu) * k + _hash_u01(_T_LFR_KIN, vidx, seed)
                    ).astype(np.int64)
    comm_of = _lfr_place(k_in, sizes, seed)
    # An odd internal stub count leaves a community one stub outward.
    odd = np.bincount(comm_of, weights=k_in, minlength=len(sizes)) % 2 == 1
    cand = np.flatnonzero(odd[comm_of] & (k_in > 0))
    k_in[cand[np.unique(comm_of[cand], return_index=True)[1]]] -= 1
    # Pair over vertices numbered by community; ``by_comm`` maps back.
    by_comm = np.argsort(comm_of, kind="stable")
    comm_sorted = comm_of[by_comm]
    bounds = np.r_[0, np.cumsum(sizes)]
    k_in, k_ex = k_in[by_comm], (k - k_in)[by_comm]
    owner = np.repeat(vidx, k_in)
    s_in, d_in, drop_in = _lfr_pair(owner, comm_sorted[owner], bounds,
                                    comm_sorted, seed, _T_LFR_PAIR_IN,
                                    _T_LFR_SWAP_IN, cross=False)
    owner = np.repeat(vidx, k_ex)
    s_ex, d_ex, drop_ex = _lfr_pair(owner, np.zeros_like(owner),
                                    np.array([0, n]), comm_sorted, seed,
                                    _T_LFR_PAIR_EX, _T_LFR_SWAP_EX,
                                    cross=True)
    return (n, by_comm[np.concatenate([s_in, s_ex])],
            by_comm[np.concatenate([d_in, d_ex])], comm_of,
            drop_in + drop_ex)


def lfr_edges(n: int, gamma: float, beta: float, mean_degree: float,
              max_degree: int, cmin: int, cmax: int, mu: float, seed: int):
    """The LFR benchmark graph (Lancichinetti, Fortunato & Radicchi, Phys.
    Rev. E 78, 046110, 2008): ``(nv, src, dst, membership)``, one record
    per undirected edge, 0-based community ids.

    1. Degrees from the power law of exponent ``gamma`` on [k_min,
       ``max_degree``], k_min solved so the mean is ``mean_degree``.
    2. Community sizes from the power law of exponent ``beta`` on
       [``cmin``, ``cmax``], summing to ``n``.
    3. Internal degree (1 - ``mu``) k, rounded at random; each vertex
       placed in a community larger than it.
    4. Internal stubs paired by a configuration model inside each
       community, external stubs by one across communities.
    5. Self-loops, multi-edges and external pairs inside one community
       re-paired in bounded rounds.

    Departures from the paper: degrees are drawn stratified (each
    uniform, together filling the law's quantiles evenly); step 5
    re-pairs and rewires in vectorized rounds (:func:`_lfr_pair`) where
    LFR rewires edge by edge, and drops the stubs the last round leaves,
    so a vertex's degree can fall short of its draw (the count is in
    :func:`synthesize`'s provenance); a community with an odd internal
    stub count turns one of them external.  No overlap.

    Deterministic: every draw is a splitmix64 hash of (seed, index) on
    the LFR stream tags.  Edges are unique, without self-loops, so unit
    weights after symmetrizing.
    """
    nv, src, dst, membership, _dropped = _lfr(
        n, gamma, beta, mean_degree, max_degree, cmin, cmax, mu, seed)
    return nv, src, dst, membership


def lfr_vertices(edges: int, mean_degree: float) -> int:
    """The LFR vertex count whose graph has about ``edges`` directed
    records: n <k> = edges."""
    return max(1, int(round(int(edges) / mean_degree)))


def lfr_realized(nv: int, src: np.ndarray, dst: np.ndarray,
                   membership: np.ndarray, dropped_stubs: int) -> dict:
    """The realized statistics of an LFR graph: mean and max degree, mu
    (the share of edge endpoints leaving their community), community
    count, and the stubs the pairing dropped."""
    deg = np.bincount(np.concatenate([src, dst]), minlength=nv)
    return {"mean_degree": float(deg.mean()), "max_degree": int(deg.max()),
            "mu": float(np.mean(membership[src] != membership[dst])),
            "communities": int(membership.max()) + 1,
            "dropped_stubs": int(dropped_stubs)}


def synthesize_graph(edges: int, seed: int = 1, profile: str = "powerlaw",
                     alpha: float = 2.3, mu: float = 0.25, dmin: int = 2,
                     edge_factor: int = 16, comm_min: int = 16,
                     comm_beta: float = 1.8, overlap: float = 0.05,
                     gamma: float = 2.0, beta: float = 1.0,
                     mean_degree: float = 20, max_degree: int = 50,
                     cmin: int = 20, cmax: int = 100):
    """In-memory variant of :func:`synthesize`: same deterministic draw
    streams, returned as a built ``core.graph.Graph`` instead of a Vite
    file — the shape serving benches and queue tests consume (ISSUE 9:
    K small graphs per process, no filesystem round-trip).  The edge
    SET matches what ``synthesize(...)`` would write for the same
    parameters (symmetrized, duplicates coalesced by Graph.from_edges).
    """
    from cuvite_tpu.core.graph import Graph

    if profile not in PROFILES:
        raise ValueError(f"unknown profile {profile!r} "
                         f"(choose from {PROFILES})")
    edges = int(edges)
    if edges < 4:
        raise ValueError("need at least 4 directed edges")
    if profile == "lfr":
        nv, src, dst, _m, _dropped = _lfr(
            lfr_vertices(edges, mean_degree), gamma, beta, mean_degree,
            max_degree, cmin, cmax, mu, seed)
        return Graph.from_edges(nv, src, dst, symmetrize=True)
    spec = SynthSpec(profile=profile, edges=edges, seed=seed, alpha=alpha,
                     mu=mu, dmin=dmin, edge_factor=edge_factor,
                     comm_min=comm_min, comm_beta=comm_beta,
                     overlap=overlap, bits64=False)
    nv, draws, bounds, _sizes = _layout(edges, spec, seed)
    srcs, dsts = [], []
    for s, d, _w in _edge_chunk_stream(nv, draws, bounds, spec,
                                       DEFAULT_CHUNK_EDGES):
        srcs.append(s)
        dsts.append(d)
    src = np.concatenate(srcs) if srcs else np.zeros(0, dtype=np.int64)
    dst = np.concatenate(dsts) if dsts else np.zeros(0, dtype=np.int64)
    return Graph.from_edges(nv, src, dst, symmetrize=True)


def churn_batches(graph, *, frac: float, seed: int = 1,
                  batches: int = 1) -> list:
    """Deterministic insert/delete churn stream against a base graph
    (ISSUE 17: the offline workload behind the warm-start A/B).

    Each batch deletes ``frac`` of the base graph's undirected pairs
    and inserts an equal count of fresh hash-drawn pairs with small
    dyadic integer weights (1..8 — inside the device coalesce's
    exactness domain, so delta-vs-rebuild stays bit-equal).  Every draw
    is a splitmix64 hash of (seed, index) on churn-only stream tags:
    the batch list is a pure function of (graph, frac, seed, batches).
    Deletes are sampled without replacement ACROSS batches (rank order
    of one hash stream over the base pairs), so batch k's deletes still
    exist when it is applied; inserts may touch any pair, including one
    another's — duplicate inserts coalesce by weight sum, exactly like
    the rebuild oracle.

    Returns a list of ``batches`` dicts with int64/f64 numpy arrays
    ``{ins_src, ins_dst, ins_w, del_src, del_dst}`` (one undirected
    record per pair; stream/DeltaBatch.from_edits symmetrizes).
    """
    frac = float(frac)
    batches = int(batches)
    if not 0.0 < frac < 1.0:
        raise ValueError("--churn fraction must be in (0, 1)")
    if batches < 1:
        raise ValueError("churn needs at least one batch")
    nv = graph.num_vertices
    deg = np.diff(graph.offsets)
    src_all = np.repeat(np.arange(nv, dtype=np.int64), deg)
    dst_all = np.asarray(graph.tails, dtype=np.int64)
    canon = src_all <= dst_all  # one record per undirected pair
    psrc, pdst = src_all[canon], dst_all[canon]
    n_pairs = len(psrc)
    n_churn = max(1, int(round(frac * n_pairs)))
    if batches * n_churn > n_pairs:
        raise ValueError(
            f"churn of {batches} x {n_churn} pairs exceeds the base "
            f"graph's {n_pairs} undirected pairs; lower --churn or "
            "--churn-batches")
    pidx = np.arange(n_pairs, dtype=np.int64)
    rank = np.argsort(splitmix64(_stream_base(_T_CHURN_DEL, seed)
                                 + pidx.astype(np.uint64)),
                      kind="stable")
    out = []
    for b in range(batches):
        dsel = rank[b * n_churn:(b + 1) * n_churn]
        # Fresh endpoints: oversample, drop self-draws, keep the first
        # n_churn — deterministic in the draw index.
        need, have, lo = n_churn, [], 0
        while need > 0:
            gidx = np.arange(lo, lo + 2 * need + 4, dtype=np.int64) \
                + np.int64(b) * np.int64(8 * (n_churn + 1))
            hu = splitmix64(_stream_base(_T_CHURN_INS, seed)
                            + (2 * gidx).astype(np.uint64))
            hv = splitmix64(_stream_base(_T_CHURN_INS, seed)
                            + (2 * gidx + 1).astype(np.uint64))
            iu = (hu % np.uint64(nv)).astype(np.int64)
            iv = (hv % np.uint64(nv)).astype(np.int64)
            keep = iu != iv
            have.append(np.stack([iu[keep], iv[keep],
                                  gidx[keep]], axis=1))
            need = n_churn - sum(len(h) for h in have)
            lo += len(gidx)
        ins = np.concatenate(have)[:n_churn]
        hw = splitmix64(_stream_base(_T_CHURN_W, seed)
                        + ins[:, 2].astype(np.uint64))
        ins_w = 1.0 + (hw % np.uint64(8)).astype(np.float64)
        out.append({
            "ins_src": ins[:, 0].copy(), "ins_dst": ins[:, 1].copy(),
            "ins_w": ins_w,
            "del_src": psrc[dsel].copy(), "del_dst": pdst[dsel].copy(),
        })
    return out


def write_churn(out_path: str, graph, *, frac: float, seed: int = 1,
                batches: int = 1) -> dict:
    """Materialize :func:`churn_batches` next to a synthesized Vite
    artifact: ``<out>.churn.npz`` holds the batch arrays
    (``{ins_src,ins_dst,ins_w,del_src,del_dst}_<k>``);
    ``<out>.churn.provenance.json`` records the churn seed/fraction and
    the npz sha256, so the acceptance A/B is reproducible offline."""
    bs = churn_batches(graph, frac=frac, seed=seed, batches=batches)
    npz_path = out_path + ".churn.npz"
    arrays = {}
    for k, b in enumerate(bs):
        for key, arr in b.items():
            arrays[f"{key}_{k}"] = arr
    np.savez(npz_path, **arrays)
    payload = {
        "source": "churn",
        "base": out_path,
        "churn_seed": int(seed),
        "churn_frac": float(frac),
        "batches": int(batches),
        "pairs_deleted_each": int(len(bs[0]["del_src"])),
        "pairs_inserted_each": int(len(bs[0]["ins_src"])),
        "sha256": _sha256_file(npz_path),
        "created": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    write_provenance(out_path + ".churn", payload)
    return payload


def load_churn(out_path: str) -> list:
    """Read ``<out>.churn.npz`` back into the churn_batches shape."""
    keys = ("ins_src", "ins_dst", "ins_w", "del_src", "del_dst")
    with np.load(out_path + ".churn.npz") as z:
        n = max(int(name.rsplit("_", 1)[1]) for name in z.files) + 1
        return [{k: z[f"{k}_{b}"] for k in keys} for b in range(n)]


def synthesize_many(
    out_prefix: str,
    count: int,
    edges: int,
    seed: int = 1,
    write_truth: bool = True,
    **kw,
) -> dict:
    """K small deterministic power-law graphs in one call (the serving
    bench/test workload): graph k is ``synthesize(...)`` under the
    distinct :func:`many_seed` stream k, written to
    ``<out_prefix>_<k>.vite``; ONE provenance file for the whole set at
    ``<out_prefix>.many.provenance.json`` (each member still gets its
    own, as every Vite artifact does)."""
    count = int(count)
    if count < 1:
        raise ValueError("--many needs a positive graph count")
    members = []
    for k in range(count):
        sk = many_seed(seed, k)
        path = f"{out_prefix}_{k:04d}.vite"
        payload = synthesize(
            path, edges, seed=sk, write_truth=write_truth,
            provenance_extra={"many": {"base_seed": seed, "index": k,
                                       "count": count}},
            **kw)
        members.append({"path": path, "seed": sk,
                        "sha256": payload["sha256"],
                        "result": payload["result"]})
    set_payload = {
        "source": "synthesized-many",
        "count": count,
        "base_seed": seed,
        "edges_each": int(edges),
        "graphs": members,
        "created": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    write_provenance(out_prefix + ".many", set_payload)
    return set_payload


def synthesize(
    out_path: str,
    edges: int,
    profile: str = "powerlaw",
    seed: int = 1,
    alpha: float = 2.3,
    mu: float = 0.25,
    dmin: int = 2,
    edge_factor: int = 16,
    comm_min: int = 16,
    comm_beta: float = 1.8,
    overlap: float = 0.05,
    gamma: float = 2.0,
    beta: float = 1.0,
    mean_degree: float = 20,
    max_degree: int = 50,
    cmin: int = 20,
    cmax: int = 100,
    bits64: bool = False,
    chunk_edges: int = DEFAULT_CHUNK_EDGES,
    write_truth: bool = True,
    provenance_extra: dict | None = None,
) -> dict:
    """Synthesize a power-law community graph as a Vite file.

    ``edges`` is the target number of DIRECTED records in the file
    (~matching a real dataset's 2x undirected edge count); the realized
    count is slightly lower (self-draws dropped; for ``lfr``, the stubs
    the pairing drops).  ``powerlaw`` reads ``alpha`` to ``overlap``;
    ``lfr`` reads ``mu`` and ``gamma`` to ``cmax`` (:func:`lfr_edges`,
    with ``n = edges / mean_degree``).  Returns the provenance payload
    (also written to ``<out>.provenance.json``).
    """
    if profile not in PROFILES:
        raise ValueError(f"unknown profile {profile!r} "
                         f"(choose from {PROFILES})")
    edges = int(edges)
    if edges < 4:
        raise ValueError("need at least 4 directed edges")
    if profile == "lfr":
        n = lfr_vertices(edges, mean_degree)
        nv, src, dst, comm_of, dropped = _lfr(
            n, gamma, beta, mean_degree, max_degree, cmin, cmax, mu, seed)
        spec = {"profile": profile, "edges": edges, "seed": seed, "n": n,
                "gamma": gamma, "beta": beta, "mean_degree": mean_degree,
                "max_degree": max_degree, "cmin": cmin, "cmax": cmax,
                "mu": mu, "bits64": bits64}
        realized = lfr_realized(nv, src, dst, comm_of, dropped)
        extra = {"lfr": realized,
                 "num_communities_planted": realized["communities"]}
        chunks = ((src[i:i + chunk_edges], dst[i:i + chunk_edges], None)
                  for i in range(0, len(src), chunk_edges))
    else:
        spec = SynthSpec(profile=profile, edges=edges, seed=seed,
                         alpha=alpha, mu=mu, dmin=dmin,
                         edge_factor=edge_factor, comm_min=comm_min,
                         comm_beta=comm_beta, overlap=overlap,
                         bits64=bits64)
        nv, draws, bounds, sizes = _layout(edges, spec, seed)
        comm_of = (np.searchsorted(bounds, np.arange(nv), side="right")
                   - 1)
        extra = {"num_communities_planted": int(len(sizes)),
                 "degree_draw_total": int(draws.sum())}
        chunks = _edge_chunk_stream(nv, draws, bounds, spec, chunk_edges)
        spec = spec.to_dict()

    stats = edges_to_vite(
        chunks, out_path, bits64=bits64, symmetrize=True, num_vertices=nv,
        relabel="none", chunk_edges=chunk_edges, fmt=f"synth:{profile}",
    )

    truth_path = None
    if write_truth:
        truth_path = out_path + ".truth"
        vidx = np.arange(nv, dtype=np.int64)
        cols = np.stack([vidx + 1, comm_of + 1], axis=1)
        np.savetxt(truth_path, cols, fmt="%d")

    payload = {
        "source": "synthesized",
        "spec": spec,
        "result": stats.to_dict(),
        **extra,
        "sha256": _sha256_file(out_path),
        "truth_path": truth_path,
        "created": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if provenance_extra:
        payload.update(provenance_extra)
    write_provenance(out_path, payload)
    return payload
