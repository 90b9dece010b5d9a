"""The LFR benchmark generator (``workloads/synth.py::lfr_edges``): the
realized statistics of the construction, determinism, the system against
the plain float64 reference on an LFR graph, and the planted structure
the first Louvain level recovers."""

import importlib.util
import json
import os

import numpy as np
import pytest

from cuvite_tpu.core.graph import Graph
from cuvite_tpu.evaluate.compare import compare_communities, load_ground_truth
from cuvite_tpu.louvain.driver import louvain_phases
from cuvite_tpu.workloads.synth import (_lfr, lfr_edges, lfr_realized,
                                        synthesize, synthesize_graph)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The paper's parameters (gamma, beta, <k>, k_max, communities 20-100).
PAPER = dict(gamma=2.0, beta=1.0, mean_degree=20, max_degree=50, cmin=20,
             cmax=100)
# First-level F-score against the planted membership at mu 0.3: ten
# seeds at n 2000 and 5000 read 0.978 to 1.0, and the same labels
# against a shuffled membership 0.011 to 0.031 (CPU).  0.95 leaves room
# below the lowest sound reading and refuses any unplanted structure.
PLANTED_F_MIN = 0.95


def _reference():
    """``benchmark/reference.py``, the plain float64 Louvain of the same
    semantics that decides the benchmark's ``correct``."""
    spec = importlib.util.spec_from_file_location(
        "bench_reference", os.path.join(ROOT, "benchmark", "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("mu", [0.1, 0.4, 0.6])
def test_realized_statistics(mu):
    n = 5000
    nv, src, dst, comm, dropped = _lfr(n, mu=mu, seed=2**33 + 3, **PAPER)
    assert nv == n
    stats = lfr_realized(nv, src, dst, comm, dropped)
    deg = np.bincount(np.concatenate([src, dst]), minlength=n)
    assert deg.max() <= PAPER["max_degree"] == stats["max_degree"]
    assert abs(stats["mean_degree"] - 20) <= 0.02 * 20, stats
    assert abs(stats["mu"] - mu) <= 0.02, stats
    # The stubs drawn (the kept edges' and the dropped) fill the law's
    # mean: the degrees are stratified draws.
    assert abs(int(deg.sum()) + dropped - 20 * n) <= 0.005 * 20 * n
    sizes = np.bincount(comm)
    assert sizes.sum() == n and len(sizes) == stats["communities"]
    assert sizes.min() >= PAPER["cmin"] and sizes.max() <= PAPER["cmax"]
    inside = comm[src] == comm[dst]
    k_in = np.bincount(np.concatenate([src[inside], dst[inside]]),
                       minlength=n)
    assert (k_in < sizes[comm]).all()
    assert not (src == dst).any()
    key = np.minimum(src, dst) * n + np.maximum(src, dst)
    assert len(np.unique(key)) == len(key)
    g = Graph.from_edges(nv, src, dst)
    assert g.num_edges == 2 * len(src) and (g.weights == 1).all()


def test_same_seed_same_edges_other_seed_other_edges():
    a = lfr_edges(3000, mu=0.4, seed=11, **PAPER)
    b = lfr_edges(3000, mu=0.4, seed=11, **PAPER)
    c = lfr_edges(3000, mu=0.4, seed=12, **PAPER)
    for x, y in zip(a[1:], b[1:]):
        assert np.array_equal(x, y)
    assert not np.array_equal(a[1], c[1]) or not np.array_equal(a[2], c[2])
    assert not np.array_equal(a[3], c[3])


def test_system_matches_the_float64_reference():
    ref = _reference()
    nv, src, dst, _comm = lfr_edges(2000, mu=0.4, seed=2**33 + 11, **PAPER)
    g = Graph.from_edges(nv, src, dst)
    res = louvain_phases(g)
    labels = np.asarray(res.communities)
    ref_labels, ref_q = ref.louvain(g)
    assert ref.adjusted_rand(labels, ref_labels) == 1.0
    q = ref.modularity(g, labels)
    assert abs(res.modularity - q) <= 1e-12
    assert abs(q - ref_q) <= 1e-12


def test_first_level_recovers_the_planted_communities():
    nv, src, dst, comm = lfr_edges(2000, mu=0.3, seed=1, **PAPER)
    labels = np.asarray(louvain_phases(Graph.from_edges(nv, src, dst),
                                       max_phases=1).communities)
    assert compare_communities(comm, labels).f_score >= PLANTED_F_MIN
    # A generator whose memberships were shuffled fails the threshold.
    shuffled = comm[np.random.default_rng(5).permutation(nv)]
    assert compare_communities(shuffled, labels).f_score < PLANTED_F_MIN


def test_synthesize_writes_vite_truth_and_provenance(tmp_path):
    from cuvite_tpu.io.vite import read_vite
    from cuvite_tpu.workloads.__main__ import main

    out = str(tmp_path / "lfr.vite")
    assert main(["synth", "--profile", "lfr", "--edges", "40000",
                 "--mu", "0.4", "--seed", "3", "--out", out]) == 0
    g = read_vite(out, bits64=False)
    truth = load_ground_truth(out + ".truth")
    with open(out + ".provenance.json", encoding="utf-8") as f:
        prov = json.load(f)
    nv, src, dst, comm = lfr_edges(2000, mu=0.4, seed=3, **PAPER)
    assert g.num_vertices == nv == len(truth) and np.array_equal(truth, comm)
    assert g.num_edges == 2 * len(src) and (g.weights == 1).all()
    assert prov["spec"]["profile"] == "lfr" and prov["spec"]["n"] == nv
    assert prov["lfr"]["communities"] == prov["num_communities_planted"]
    assert set(prov["lfr"]) == {"mean_degree", "max_degree", "mu",
                                "communities", "dropped_stubs"}
    mem = synthesize_graph(40000, seed=3, profile="lfr", mu=0.4)
    assert np.array_equal(mem.offsets, g.offsets)
    assert np.array_equal(mem.tails, g.tails)
    p2 = synthesize(str(tmp_path / "again.vite"), 40000, profile="lfr",
                    seed=3, mu=0.4)
    assert p2["sha256"] == prov["sha256"]


def test_lfr_refuses_parameters_with_no_graph():
    with pytest.raises(ValueError):
        lfr_edges(1000, mu=1.5, seed=1, **PAPER)
    with pytest.raises(ValueError):
        lfr_edges(1000, mu=0.1, seed=1, **dict(PAPER, cmax=30))
    with pytest.raises(ValueError):
        lfr_edges(1000, mu=0.4, seed=1, **dict(PAPER, mean_degree=60))
