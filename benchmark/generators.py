"""The benchmark's own graph generators: copies, so no PR can move the traffic.

A configuration's ``generator`` names its ``kind``; ``graphs/<kind>.py``
draws the edges, ``edges(**params) -> (nv, src, dst)``, as a pure
function of its parameters and its own ``seed``.  A new kind of graph is
a new file there.  Today: ``rmat``, the Graph500 Kronecker/R-MAT
generator of ``cuvite_tpu/io/generate.py::rmat_edges_numpy``.

A configuration's graph is drawn from its generator's own ``seed``,
which also scrambles its vertex ids.  The run's ``--seed`` draws the
order of each vertex's edge list: the same graph, in another order.  The
answer does not depend on that order, so every seed does the same work;
a permutation of the vertex ids would not do (parallel Louvain breaks
ties by id, so its iteration count, its coarse shapes and so its
compiles change with the ids).

Everything here is numpy; nothing of the program is imported.  The CSR
that ``build_csr`` returns is what both the program (wrapped in its
``Graph``) and the reference read.
"""

from __future__ import annotations

import dataclasses
import importlib

import numpy as np

_SM_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
_SM_C1 = np.uint64(0xBF58476D1CE4E5B9)
_SM_C2 = np.uint64(0x94D049BB133111EB)
_MASK64 = (1 << 64) - 1
_STRIDE = 0x9E3779B97F4A7C15

# The benchmark's own streams: the vertex scramble, the run seed's
# edge-list order.
_T_PERM = 0x0D << 56
_T_ROWS = 0x0E << 56


def _splitmix64_inplace(x: np.ndarray, tmp: np.ndarray) -> np.ndarray:
    """``splitmix64`` into ``x`` itself (``tmp`` is scratch)."""
    with np.errstate(over="ignore"):
        x += _SM_GOLDEN
        np.right_shift(x, np.uint64(30), out=tmp)
        x ^= tmp
        x *= _SM_C1
        np.right_shift(x, np.uint64(27), out=tmp)
        x ^= tmp
        x *= _SM_C2
        np.right_shift(x, np.uint64(31), out=tmp)
        x ^= tmp
    return x


def splitmix64(x) -> np.ndarray:
    """Vectorized SplitMix64 finalizer over uint64 (wrapping)."""
    x = np.array(x, dtype=np.uint64)
    return _splitmix64_inplace(x, np.empty_like(x))


def _stream_base(tag: int, seed: int) -> np.uint64:
    return np.uint64((seed * _STRIDE + tag) & _MASK64)


@dataclasses.dataclass
class GraphData:
    """A symmetric CSR graph (both directions stored, duplicates summed)."""

    offsets: np.ndarray   # int64 [nv+1]
    tails: np.ndarray     # int32 [ne]
    weights: np.ndarray   # float32 [ne]

    @property
    def num_vertices(self) -> int:
        return len(self.offsets) - 1

    @property
    def num_edges(self) -> int:
        return len(self.tails)

    def sources(self) -> np.ndarray:
        return np.repeat(np.arange(self.num_vertices, dtype=np.int64),
                         np.diff(self.offsets))


def build_csr(nv: int, src: np.ndarray, dst: np.ndarray) -> GraphData:
    """Symmetrize (u, v) and (v, u), drop self-draws, sum duplicates."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    key = np.concatenate([src * nv + dst, dst * nv + src])
    key.sort()
    first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    uniq = key[first]
    counts = np.diff(np.r_[first, len(key)])
    s = uniq // nv
    offsets = np.zeros(nv + 1, dtype=np.int64)
    np.cumsum(np.bincount(s, minlength=nv), out=offsets[1:])
    return GraphData(offsets=offsets, tails=(uniq % nv).astype(np.int32),
                     weights=counts.astype(np.float32))


def edges_of(kind: str):
    """``graphs/<kind>.py``'s ``edges``."""
    return importlib.import_module(f"benchmark.graphs.{kind}").edges


def permutation(nv: int, seed: int) -> np.ndarray:
    """A random permutation of [0, nv) drawn from ``seed``."""
    idx = np.arange(nv, dtype=np.uint64)
    return np.argsort(splitmix64(_stream_base(_T_PERM, int(seed)) + idx),
                      kind="stable").astype(np.int64)


def shuffle_rows(g: GraphData, seed: int) -> GraphData:
    """Each vertex's edge list in an order drawn from ``seed``."""
    src = g.sources().astype(np.uint64)
    h = splitmix64(_stream_base(_T_ROWS, int(seed))
                   + np.arange(g.num_edges, dtype=np.uint64))
    order = np.argsort((src << np.uint64(32)) | (h >> np.uint64(32)))
    return GraphData(offsets=g.offsets, tails=g.tails[order],
                     weights=g.weights[order])


def make_graph(generator: dict, seed: int) -> GraphData:
    """The configuration's graph, its vertex ids scrambled by the
    generator's own seed (so the graph and its order are the
    configuration's), each edge list in the order the run seed draws."""
    params = dict(generator)
    kind = params.pop("kind")
    nv, src, dst = edges_of(kind)(**params)
    perm = permutation(nv, params["seed"])
    return shuffle_rows(build_csr(nv, perm[src], perm[dst]), seed)
