"""The benchmark's generators are copies of the program's, draw for
draw, found by the kind a configuration names, and the run seed only
orders each vertex's edge list."""

import numpy as np

from benchmark import generators

RMAT = {"kind": "rmat", "scale": 12, "edge_factor": 16, "seed": 1,
        "a": 0.57, "b": 0.19, "c": 0.19}


def _edge_keys(offsets, tails, weights, perm=None):
    nv = len(offsets) - 1
    src = np.repeat(np.arange(nv), np.diff(offsets))
    dst = np.asarray(tails, dtype=np.int64)
    if perm is not None:
        inv = np.argsort(perm)
        src, dst = inv[src], inv[dst]
    order = np.argsort(src * nv + dst)
    return (src * nv + dst)[order], np.asarray(weights)[order]


def test_same_graph_as_the_program():
    from cuvite_tpu.io.generate import generate_rmat

    seed = 2**33 + 5  # larger than 32 signed bits hold
    g = generators.make_graph(RMAT, seed)
    ref = generate_rmat(RMAT["scale"], RMAT["edge_factor"], seed=RMAT["seed"])
    perm = generators.permutation(g.num_vertices, RMAT["seed"])
    k1, w1 = _edge_keys(g.offsets, g.tails, g.weights, perm)
    k2, w2 = _edge_keys(ref.offsets, ref.tails, ref.weights)
    assert np.array_equal(k1, k2)
    assert np.array_equal(w1, w2)


def test_kind_is_found_by_name():
    from benchmark.graphs import rmat

    assert generators.edges_of("rmat") is rmat.edges


def test_seed_orders_edge_lists_only():
    a = generators.make_graph(RMAT, 1)
    b = generators.make_graph(RMAT, 2**40 + 1)
    assert np.array_equal(a.offsets, b.offsets)
    assert not np.array_equal(a.tails, b.tails)
    k1, w1 = _edge_keys(a.offsets, a.tails, a.weights)
    k2, w2 = _edge_keys(b.offsets, b.tails, b.weights)
    assert np.array_equal(k1, k2) and np.array_equal(w1, w2)
    again = generators.make_graph(RMAT, 1)
    assert np.array_equal(a.tails, again.tails)


def test_answer_does_not_depend_on_the_seed():
    from benchmark import reference

    labels = [reference.louvain(generators.make_graph(RMAT, s))[0]
              for s in (3, 2**33 + 1)]
    assert np.array_equal(*labels)
