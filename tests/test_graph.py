"""Unit tests: CSR construction, invariants, Vite I/O round-trip."""

import numpy as np
import pytest

from cuvite_tpu.core.distgraph import DistGraph, balanced_parts, uniform_parts
from cuvite_tpu.core.graph import Graph
from cuvite_tpu.io.vite import read_vite, write_vite


def test_from_edges_symmetrize(two_cliques):
    g = two_cliques
    assert g.num_vertices == 10
    # 2*K5 (10 undirected each) + bridge = 21 undirected -> 42 directed slots
    assert g.num_edges == 42
    # Sum of weighted degrees = 2m
    assert g.total_edge_weight_twice() == 42.0
    np.testing.assert_array_equal(
        g.degrees(), np.array([5, 4, 4, 4, 4, 5, 4, 4, 4, 4])
    )


def test_weighted_degrees_match_manual(karate):
    g = karate
    wd = g.weighted_degrees()
    manual = np.zeros(g.num_vertices)
    for v in range(g.num_vertices):
        e0, e1 = g.offsets[v], g.offsets[v + 1]
        manual[v] = g.weights[e0:e1].sum()
    np.testing.assert_allclose(wd, manual, rtol=1e-6)
    assert wd.sum() == g.total_edge_weight_twice()


def test_self_loop_single_insertion():
    g = Graph.from_edges(3, [0, 1, 1], [1, 2, 1])
    # self loop (1,1) inserted once; (0,1) and (1,2) symmetrized
    assert g.num_edges == 5
    assert g.weighted_degrees()[1] == 3.0


def test_duplicate_edges_coalesce():
    g = Graph.from_edges(2, [0, 0], [1, 1])
    assert g.num_edges == 2  # one per direction
    np.testing.assert_allclose(g.weights, [2.0, 2.0])


@pytest.mark.parametrize("bad", [-1.0, np.nan])
def test_negative_or_nan_weight_refused(bad):
    """The step's sorted dedup needs non-negative weights; a Graph, which
    every path into it starts from, refuses anything else."""
    with pytest.raises(ValueError, match="non-negative"):
        Graph.from_edges(3, [0, 1], [1, 2], weights=[1.0, bad])
    with pytest.raises(ValueError, match="non-negative"):
        Graph(np.array([0, 1, 1, 1]), np.array([1]), np.array([bad]))
    assert Graph.from_edges(3, [0, 1], [1, 2], weights=[1.0, 0.0]).num_edges


def test_vite_roundtrip(tmp_path, karate):
    for bits64 in (True, False):
        p = str(tmp_path / f"karate{bits64}.bin")
        write_vite(p, karate, bits64=bits64)
        g2 = read_vite(p, bits64=bits64)
        assert g2.num_vertices == karate.num_vertices
        assert g2.num_edges == karate.num_edges
        np.testing.assert_array_equal(g2.offsets, karate.offsets)
        np.testing.assert_array_equal(g2.tails, karate.tails)
        np.testing.assert_allclose(g2.weights, karate.weights)


def test_vite_sliced_read(tmp_path, karate):
    p = str(tmp_path / "karate.bin")
    write_vite(p, karate, bits64=True)
    lo, hi = 10, 20
    g2 = read_vite(p, bits64=True, vertex_range=(lo, hi))
    assert g2.num_vertices == hi - lo
    assert g2.offsets[0] == 0
    e0, e1 = karate.offsets[lo], karate.offsets[hi]
    np.testing.assert_array_equal(g2.tails, karate.tails[e0:e1])


def test_uniform_parts():
    p = uniform_parts(10, 4)
    np.testing.assert_array_equal(p, [0, 3, 6, 8, 10])


def test_balanced_parts_cover(karate):
    p = balanced_parts(karate, 4)
    assert p[0] == 0 and p[-1] == karate.num_vertices
    assert np.all(np.diff(p) >= 0)


def test_distgraph_shards_cover_all_edges(karate):
    for nshards in (1, 2, 4):
        dg = DistGraph.build(karate, nshards)
        total_real = sum(sh.n_real_edges for sh in dg.shards)
        assert total_real == karate.num_edges
        # Padding has zero weight; real weights survive intact.
        src, dst, w = dg.stacked_edges()
        assert w.astype(np.float64).sum() == karate.total_edge_weight_twice()
        # Padded id round trip.
        assert np.all(dg.pad_to_old[dg.old_to_pad] == np.arange(34))
        # vdeg preserved in padded space
        np.testing.assert_allclose(
            dg.padded_weighted_degrees()[dg.old_to_pad],
            karate.weighted_degrees(), rtol=1e-6,
        )
