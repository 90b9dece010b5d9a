"""Host-side CSR graph container.

Equivalent of the reference `Graph` (/root/reference/graph.hpp:27-57): an
adjacency structure `edgeListIndexes[nv+1]` plus an edge array of
`{tail, weight}` pairs.  Here the struct-of-arrays layout is native: separate
`offsets`, `tails`, `weights` numpy arrays, which is also exactly the layout
device kernels want.

Graphs are undirected and stored with both directions present (the Vite
binary format stores each undirected edge twice, once per endpoint), so
``sum(weights) == 2m`` and per-vertex weighted degree is a plain segment sum.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from cuvite_tpu.core.types import Policy, default_policy


@dataclasses.dataclass
class Graph:
    """CSR graph: ``offsets[nv+1]``, ``tails[ne]``, ``weights[ne]``."""

    offsets: np.ndarray  # [nv+1] vertex dtype
    tails: np.ndarray    # [ne]   vertex dtype (global ids)
    weights: np.ndarray  # [ne]   weight dtype
    policy: Policy = dataclasses.field(default_factory=default_policy)

    def __post_init__(self) -> None:
        self.offsets = np.ascontiguousarray(self.offsets, dtype=np.int64)
        self.tails = np.ascontiguousarray(self.tails, dtype=self.policy.vertex_dtype)
        self.weights = np.ascontiguousarray(self.weights, dtype=self.policy.weight_dtype)
        # Every path into the Louvain step starts from a Graph, and the
        # step's sorted dedup reads run sums off suffix sums that must not
        # increase along a row (louvain/bucketed.py::_row_argmax_sorted).
        if not np.all(self.weights >= 0):
            raise ValueError("edge weights must be non-negative numbers")

    @property
    def num_vertices(self) -> int:
        return len(self.offsets) - 1

    @property
    def num_edges(self) -> int:
        """Number of directed edge slots (2x the undirected edge count)."""
        return len(self.tails)

    def degrees(self) -> np.ndarray:
        """Per-vertex edge counts."""
        return np.diff(self.offsets)

    def weighted_degrees(self) -> np.ndarray:
        """Per-vertex sum of incident edge weights, self-loops included
        (cf. distSumVertexDegree, /root/reference/louvain.cpp:2126-2151)."""
        from cuvite_tpu import native

        if self.num_edges >= native.MIN_NATIVE_EDGES and native.available():
            # Same f64 slab-order accumulation, without materializing the
            # expanded O(E) source array + f64 weight copy.
            return native.weighted_degrees(
                self.offsets, self.weights).astype(self.policy.weight_dtype)
        return np.bincount(
            self.sources(), weights=self.weights.astype(np.float64),
            minlength=self.num_vertices,
        ).astype(self.policy.weight_dtype)

    def sources(self) -> np.ndarray:
        """Per-edge source vertex id (the CSR row expanded)."""
        return np.repeat(
            np.arange(self.num_vertices, dtype=self.policy.vertex_dtype),
            self.degrees(),
        )

    def total_edge_weight_twice(self) -> float:
        """Sigma of all weighted degrees = 2m; the reciprocal is the gain
        constant (cf. distCalcConstantForSecondTerm,
        /root/reference/louvain.cpp:2153-2183)."""
        return float(self.weights.sum(dtype=np.float64))

    @staticmethod
    def from_edges(
        num_vertices: int,
        src: np.ndarray,
        dst: np.ndarray,
        weights: np.ndarray | None = None,
        symmetrize: bool = True,
        policy: Policy | None = None,
    ) -> "Graph":
        """Build a CSR graph from an edge list.

        With ``symmetrize=True`` each input edge (u, v), u != v, is inserted
        in both directions; self-loops are inserted once.  Duplicate edges are
        coalesced by summing weights.
        """
        policy = policy or default_policy()
        from cuvite_tpu import native

        # Unit-weight fast path (weights=None: R-MAT, unweighted inputs):
        # the int32 native builder counts duplicates instead of summing f64
        # ones — no 8-byte array exists anywhere, which is what makes
        # single-host ingest of billion-edge unweighted graphs fit
        # (tools/scale_model.md).  Output is bit-identical to the generic
        # path after the policy cast (exact integer counts, rounded once)
        # — which requires the policy weight dtype to BE f32: a wide
        # (f64) policy must keep the generic f64 path or duplicate counts
        # above 2^24 would round.
        if (weights is None and len(src) >= native.MIN_NATIVE_EDGES
                and native.available() and num_vertices <= 1 << 31
                and policy.weight_dtype == np.float32):
            offsets, tails, wcnt = native.build_csr_unit(
                num_vertices, src, dst, symmetrize
            )
            return Graph(
                offsets=offsets,
                tails=tails.astype(policy.vertex_dtype, copy=False),
                weights=wcnt.astype(policy.weight_dtype, copy=False),
                policy=policy,
            )
        # Weighted low-footprint path (benchmark-scale weighted ingest,
        # VERDICT r3 item 8): the sort carries an int32 original-edge
        # index, never the f64 weights, and emits int32/f32 directly —
        # ~24 B/slot transient vs the generic path's 32, with int64
        # src/dst accepted as-is (no width conversion).  Output is
        # bit-identical to the generic path + policy cast (accumulation
        # order preserved by sort stability).  Small nv keeps the generic
        # route, whose dense counting path wins there.
        if (weights is not None and len(src) >= native.MIN_NATIVE_EDGES
                and native.available()
                and (1 << 22) < num_vertices <= (1 << 31)
                and policy.weight_dtype == np.float32
                and (2 * len(src) if symmetrize else len(src))
                < (1 << 31)):
            offsets, tails, w32 = native.build_csr_w(
                num_vertices, src, dst, weights, symmetrize
            )
            return Graph(
                offsets=offsets,
                tails=tails.astype(policy.vertex_dtype, copy=False),
                weights=w32,
                policy=policy,
            )
        src = np.asarray(src, dtype=np.int64)
        dst = np.asarray(dst, dtype=np.int64)
        # Accumulate duplicate-edge sums from the raw f64 weights; the cast
        # to the policy dtype happens once, on the coalesced result (same
        # contract as the native builder, native/cuvite_native.cpp).
        if weights is None:
            w = np.ones(len(src), dtype=np.float64)
        else:
            w = np.asarray(weights, dtype=np.float64)

        # The native builder's composite radix key src*nv+dst only fits
        # uint64 for nv <= 2^32; beyond that use the numpy path.
        if (len(src) >= native.MIN_NATIVE_EDGES and native.available()
                and num_vertices <= 1 << 32):
            offsets, tails, wsum = native.build_csr(
                num_vertices, src, dst, w, symmetrize
            )
            return Graph(
                offsets=offsets,
                tails=tails.astype(policy.vertex_dtype),
                weights=wsum.astype(policy.weight_dtype),
                policy=policy,
            )
        if symmetrize:
            keep = src != dst
            src2 = np.concatenate([src, dst[keep]])
            dst2 = np.concatenate([dst, src[keep]])
            w2 = np.concatenate([w, w[keep]])
        else:
            src2, dst2, w2 = src, dst, w
        # Coalesce duplicates and sort into CSR order.
        key = src2 * np.int64(num_vertices) + dst2
        order = np.argsort(key, kind="stable")
        key, src2, dst2, w2 = key[order], src2[order], dst2[order], w2[order]
        uniq_mask = np.ones(len(key), dtype=bool)
        uniq_mask[1:] = key[1:] != key[:-1]
        seg_ids = np.cumsum(uniq_mask) - 1
        n_uniq = int(seg_ids[-1]) + 1 if len(seg_ids) else 0
        w_out = np.zeros(n_uniq, dtype=np.float64)
        np.add.at(w_out, seg_ids, w2.astype(np.float64))
        src_u = src2[uniq_mask]
        dst_u = dst2[uniq_mask]
        counts = np.bincount(src_u, minlength=num_vertices)
        offsets = np.zeros(num_vertices + 1, dtype=np.int64)
        np.cumsum(counts, out=offsets[1:])
        return Graph(
            offsets=offsets,
            tails=dst_u.astype(policy.vertex_dtype),
            weights=w_out.astype(policy.weight_dtype),
            policy=policy,
        )
