"""The plain reference that decides ``correct``: numpy, float64, and nothing
of the program.

A clustering is an answer that can be checked on its own.  The reference
is a plain numpy implementation of the same parallel Louvain (Vite's
sweep, threshold loop and coarsening; ``louvain``), in float64, run on
the benchmark's own CSR (``generators.py``).  Over each answer of the
timed path, labels and the modularity it reported, it reads:

- ``q_gap``: |reported Q - Q of the labels|, Q computed here in float64.
  The driver states an f64-class reported modularity.
- ``q_ref_gap``: |Q of the labels - Q of the reference's own labels|.
- ``ref_miss``: 1 - adjusted Rand index of the labels against the
  reference's labels (0 where they are the same partition).
- ``bad_labels``: vertices without a valid label (0 for a partition).

Two controls put the reference in the program's place, each a step below
a precision the configuration states (``faults.py``): every sum in
float32 (``louvain(..., dtype=np.float32)``), below the float64 of the
reported Q; and each move gain in bfloat16 (``louvain(...,
gain_dtype=ml_dtypes.bfloat16)``), below the float32 of the program's
gains.
"""

from __future__ import annotations

import numpy as np


def modularity(g, labels: np.ndarray, dtype=np.float64) -> float:
    """Q = sum_c e_c / 2m - sum_c (a_c / 2m)^2 over directed edge slots,
    every sum in ``dtype``."""
    labels = np.asarray(labels, dtype=np.int64)
    src_c = labels[g.sources()]
    dst_c = labels[g.tails]
    w = g.weights.astype(dtype)
    two_m = w.sum(dtype=dtype)
    e_in = w[src_c == dst_c].sum(dtype=dtype)
    a_c = np.bincount(src_c, weights=w, minlength=int(labels.max()) + 1)
    a_c = a_c.astype(dtype)
    frac = a_c / two_m
    return float(e_in / two_m - (frac * frac).sum(dtype=dtype))


def bad_labels(g, labels) -> int:
    """Vertices without a valid label (0 for a partition of every vertex)."""
    labels = np.asarray(labels)
    if labels.shape != (g.num_vertices,):
        return g.num_vertices
    if not np.issubdtype(labels.dtype, np.integer):
        return g.num_vertices
    return int((labels < 0).sum())


def _rounder(gain_dtype):
    """Rounds an array to ``gain_dtype`` and back (None: no rounding)."""
    if gain_dtype is None:
        return lambda x: x
    return lambda x: np.asarray(x).astype(gain_dtype).astype(np.float64)


def _step(src, dst, w, nv, comm, k, self_loop, const, dt, gain_dtype=None):
    """One synchronous sweep of the parallel Louvain step (Vite's
    distExecuteLouvainIteration / distGetMaxIndex): every vertex takes
    its best neighbouring community by
    gain(i -> y) = 2 (e_iy - e_ix) - 2 k_i (a_y - a_x) / 2m,
    e_ix without self-loops, a_x = deg(x) - k_i; only gains > 0 move,
    ties go to the smaller community, and two singletons never merge
    upward.  With ``gain_dtype``, every operand and every operation of
    the gain is rounded to it.  Returns (targets, modularity of the input
    assignment)."""
    a = np.bincount(comm, weights=k, minlength=nv).astype(dt)
    size = np.bincount(comm, minlength=nv)
    cs, cd = comm[src], comm[dst]
    zero = dt(0.0)
    counter0 = np.bincount(src, weights=np.where(cs == cd, w, zero),
                           minlength=nv).astype(dt)
    eix = counter0 - self_loop
    key = src * nv + cd
    order = np.argsort(key, kind="stable")
    key = key[order]
    first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    eiy = np.add.reduceat(w[order], first).astype(dt)
    key = key[first]
    v, y = key // nv, key % nv
    valid = y != comm[v]
    v, y, eiy = v[valid], y[valid], eiy[valid]
    targets = comm.copy()
    if len(v):
        two = dt(2.0)
        if gain_dtype is None:
            gain = (two * (eiy - eix[v])
                    - two * k[v] * (a[y] - (a[comm[v]] - k[v])) * const)
        else:
            r = _rounder(gain_dtype)
            kv = r(k[v])
            d_e = r(r(eiy) - r(eix[v]))
            d_a = r(r(a[y]) - r(r(a[comm[v]]) - kv))
            gain = r(two * d_e - r(r(two * kv * d_a) * r(const)))
        # (v, y) pairs come sorted by v, then y: per-vertex runs.
        starts = np.flatnonzero(np.r_[True, v[1:] != v[:-1]])
        best = np.maximum.reduceat(gain, starts)
        rows = v[starts]
        at_best = gain == np.repeat(best, np.diff(np.r_[starts, len(v)]))
        big = np.iinfo(np.int64).max
        best_y = np.minimum.reduceat(np.where(at_best, y, big), starts)
        guard = ((size[best_y] == 1) & (size[comm[rows]] == 1)
                 & (best_y > comm[rows]))
        move = (best > 0.0) & ~guard
        targets[rows[move]] = best_y[move]
    q = (counter0.sum(dtype=dt) * const
         - (a * a).sum(dtype=dt) * const * const)
    return targets, q


def _phase(src, dst, w, nv, threshold, max_iters, dt, gain_dtype):
    """One phase (louvain.cpp:471-588): sweep until the modularity gain
    drops below the threshold; keep the last assignment that gained."""
    w = w.astype(dt)
    k = np.bincount(src, weights=w, minlength=nv).astype(dt)
    self_loop = np.bincount(src, weights=np.where(src == dst, w, dt(0.0)),
                            minlength=nv).astype(dt)
    const = dt(1.0) / w.sum(dtype=dt)
    comm = np.arange(nv, dtype=np.int64)
    past, prev, iters = comm, -1.0, 0
    while True:
        target, q = _step(src, dst, w, nv, comm, k, self_loop, const, dt,
                          gain_dtype)
        iters += 1
        if q - prev < threshold:
            break
        prev, past, comm = max(q, -1.0), comm, target
        if iters >= max_iters:
            break
    return past, iters


def louvain(g, threshold: float = 1.0e-6, max_phases: int = 200,
            max_iters: int = 10_000, dtype=np.float64,
            gain_dtype=None) -> tuple:
    """Multi-phase parallel Louvain (main.cpp:218-495): phases until one
    gains no more than ``threshold``; between phases the graph is
    coarsened to its communities (edge weights summed, a community's
    internal weight kept as its self-loop).  Every gain and modularity is
    computed in ``dtype``, and each gain rounded to ``gain_dtype`` where
    one is given (the controls).  Returns (labels, Q)."""
    dt = np.dtype(dtype).type
    src = g.sources()
    dst = g.tails.astype(np.int64)
    w = g.weights.astype(np.float64)
    nv = g.num_vertices
    comm_all = np.arange(nv, dtype=np.int64)
    prev_q, phase, total = -1.0, 0, 0
    while phase < max_phases and total <= max_iters:
        comm, iters = _phase(src, dst, w, nv, threshold, max_iters, dt,
                             gain_dtype)
        total += iters
        q = _modularity_edges(src, dst, w.astype(dt), comm, dt)
        if q - prev_q <= threshold:
            break
        uniq, dense = np.unique(comm, return_inverse=True)
        comm_all = dense[comm_all]
        nc = len(uniq)
        key, inv = np.unique(dense[src] * nc + dense[dst],
                             return_inverse=True)
        w = np.bincount(inv, weights=w)
        src, dst, nv = key // nc, key % nc, nc
        prev_q = q
        phase += 1
    return np.unique(comm_all, return_inverse=True)[1], prev_q


def _modularity_edges(src, dst, w, comm, dt) -> float:
    two_m = w.sum(dtype=dt)
    frac = np.bincount(comm[src], weights=w).astype(dt) / two_m
    return float(w[comm[src] == comm[dst]].sum(dtype=dt) / two_m
                 - (frac * frac).sum(dtype=dt))


def _pairs(counts: np.ndarray) -> float:
    counts = counts.astype(np.float64)
    return float((counts * (counts - 1.0) / 2.0).sum())


def adjusted_rand(labels: np.ndarray, truth: np.ndarray) -> float:
    """Adjusted Rand index of two partitions of the same vertices."""
    labels = np.asarray(labels, dtype=np.int64)
    truth = np.asarray(truth, dtype=np.int64)
    n = len(labels)
    nt = int(truth.max()) + 1
    _, joint = np.unique(labels * nt + truth, return_counts=True)
    sum_ij = _pairs(joint)
    sum_a = _pairs(np.bincount(labels))
    sum_b = _pairs(np.bincount(truth))
    expected = sum_a * sum_b / (n * (n - 1) / 2.0)
    top = 0.5 * (sum_a + sum_b)
    if top == expected:
        return 1.0
    return (sum_ij - expected) / (top - expected)


def label_numbers(g, labels, ref) -> dict:
    """The numbers that depend on the labels alone; ``ref`` is the
    reference's own answer, (labels, Q), on the same graph."""
    bad = bad_labels(g, labels)
    out = {"bad_labels": float(bad)}
    if bad:
        return out
    out["q"] = modularity(g, labels)
    out["q_ref_gap"] = abs(out["q"] - ref[1])
    out["ref_miss"] = 1.0 - adjusted_rand(labels, ref[0])
    return out


def compared(label_nums: dict, reported_q: float) -> dict:
    """One answer's compared numbers: its labels' numbers and the gap
    between the Q it reported and the Q of its labels."""
    out = dict(label_nums)
    if "q" in out:
        out["q_gap"] = abs(float(reported_q) - out["q"])
    return out


def judge(numbers: dict, limits: dict) -> list:
    """The compared numbers beyond their limits (empty: correct).  A
    limit without its number is a failure too: the check did not run."""
    return [name for name, limit in limits.items()
            if not numbers.get(name, float("inf")) <= limit]
