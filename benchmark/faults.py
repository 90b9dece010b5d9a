"""Faults planted under the timed path of the ``clustering`` loop, and
its controls, for setting and proving the limits of ``correct``
(control.py, tests/).

Each stands in for ``loops/clustering.py::system`` (graph, tracer) ->
(labels, reported Q, result-like object):

- ``unchanged``: a step that returns its state unchanged: every phase
  keeps the identity assignment, so every vertex stays alone, and the
  reported Q is that of those labels;
- ``altered``: the program's answer with one vertex in a hundred moved to
  another community where it is produced, its reported Q kept;
- ``half_edges``: every other undirected edge left out of what the
  program sees; the answer is judged on the whole graph;
- ``control_f32``: the plain reference with every sum in float32, a step
  below the float64 the configuration states for the reported Q (its
  labels and its own float32 Q);
- ``control_bf16``: the plain reference with each move gain in bfloat16,
  a step below the float32 the configuration states for the gains (its
  labels, and their Q in float64: only the labels can fail it).
"""

from __future__ import annotations

import types

import ml_dtypes
import numpy as np

from benchmark import generators, reference
from benchmark.loops import clustering


def _result(labels, q, iterations=0):
    return types.SimpleNamespace(communities=labels, modularity=q,
                                 phases=[], total_iterations=iterations)


def unchanged(graph, tracer):
    labels = np.arange(graph.num_vertices, dtype=np.int64)
    q = reference.modularity(graph, labels)
    return labels, q, _result(labels, q)


def altered(graph, tracer):
    labels, q, res = clustering.system(graph, tracer)
    labels = labels.copy()
    idx = np.arange(0, len(labels), 100)
    labels[idx] = (labels[idx] + 1) % (int(labels.max()) + 1)
    return labels, q, res


def half_graph(graph):
    """Every other undirected pair of ``graph``, both directions kept."""
    src = graph.sources()
    dst = graph.tails.astype(np.int64)
    up = src < dst
    s, d = src[up][::2], dst[up][::2]
    return generators.build_csr(graph.num_vertices, s, d)


def half_edges(graph, tracer):
    return clustering.system(half_graph(graph), tracer)


def control_f32(graph, tracer):
    labels, q = reference.louvain(graph, dtype=np.float32)
    return labels, q, _result(labels, q)


def control_bf16(graph, tracer):
    labels, _q = reference.louvain(graph, gain_dtype=ml_dtypes.bfloat16)
    q = reference.modularity(graph, labels)
    return labels, q, _result(labels, q)


CONTROLS = {"control_f32": control_f32, "control_bf16": control_bf16}
FAULTS = {"unchanged": unchanged, "altered": altered,
          "half_edges": half_edges}
