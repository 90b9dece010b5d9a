"""The LFR generator of the benchmark is the program's, draw for draw; the
run seed only orders edge lists; and the ``lfr-5e5.cluster`` cell, cut to
a tiny graph, is correct when sound and refuses each control and fault."""

import argparse
import os

import numpy as np
import pytest

from benchmark import faults, generators, harness

CELL = "lfr-5e5.cluster"
LFR = {"kind": "lfr", "n": 2000, "gamma": 2.0, "beta": 1.0,
       "mean_degree": 20, "max_degree": 50, "cmin": 20, "cmax": 100,
       "mu": 0.4, "seed": 1}


def _edge_keys(offsets, tails, weights, perm=None):
    nv = len(offsets) - 1
    src = np.repeat(np.arange(nv), np.diff(offsets))
    dst = np.asarray(tails, dtype=np.int64)
    if perm is not None:
        inv = np.argsort(perm)
        src, dst = inv[src], inv[dst]
    order = np.argsort(src * nv + dst)
    return (src * nv + dst)[order], np.asarray(weights)[order]


@pytest.mark.parametrize("mu,seed", [(0.4, 2**33 + 5), (0.1, 7)])
def test_same_draws_as_the_program(mu, seed):
    from benchmark.graphs import lfr
    from cuvite_tpu.workloads.synth import lfr_edges

    params = dict(LFR, mu=mu, seed=seed)
    params.pop("kind")
    mine = lfr.edges(**params)
    theirs = lfr_edges(**params)
    assert mine[0] == theirs[0]
    assert np.array_equal(mine[1], theirs[1])
    assert np.array_equal(mine[2], theirs[2])


def test_same_graph_as_the_program():
    from cuvite_tpu import Graph
    from cuvite_tpu.workloads.synth import lfr_edges

    g = generators.make_graph(LFR, 2**33 + 9)
    params = dict(LFR)
    params.pop("kind")
    nv, src, dst, _comm = lfr_edges(**params)
    ref = Graph.from_edges(nv, src, dst)
    perm = generators.permutation(g.num_vertices, LFR["seed"])
    k1, w1 = _edge_keys(g.offsets, g.tails, g.weights, perm)
    k2, w2 = _edge_keys(ref.offsets, ref.tails, ref.weights)
    assert np.array_equal(k1, k2)
    assert np.array_equal(w1, w2) and (w1 == 1).all()


def test_kind_is_found_by_name():
    from benchmark.graphs import lfr

    assert generators.edges_of("lfr") is lfr.edges


def test_seed_orders_edge_lists_only():
    a = generators.make_graph(LFR, 1)
    b = generators.make_graph(LFR, 2**40 + 1)
    assert np.array_equal(a.offsets, b.offsets)
    assert not np.array_equal(a.tails, b.tails)
    k1, w1 = _edge_keys(a.offsets, a.tails, a.weights)
    k2, w2 = _edge_keys(b.offsets, b.tails, b.weights)
    assert np.array_equal(k1, k2) and np.array_equal(w1, w2)
    again = generators.make_graph(LFR, 1)
    assert np.array_equal(a.tails, again.tails)


def test_config_is_the_published_benchmark():
    spec = harness.load_spec(CELL)
    gen = spec["config"]["generator"]
    assert {k: gen[k] for k in ("gamma", "beta", "mean_degree",
                                "max_degree", "cmin", "cmax")} == {
        "gamma": 2.0, "beta": 1.0, "mean_degree": 20, "max_degree": 50,
        "cmin": 20, "cmax": 100}
    assert spec["config"]["reduced"] == {}
    assert spec["cell"]["chips"] == 1 and spec["cell"]["traffic"] == "cluster"
    assert {m["name"] for m in spec["per_layer"]} == {
        "plan_s", "iterate_s", "coarsen_s", "step_roofline",
        "idle_share.cluster", "evaluate_s", "partition_s", "bucket_s",
        "unstaged_idle_s"}


def _run(system=None):
    spec = harness.load_spec(CELL)
    spec["config"]["generator"].update(n=LFR["n"])
    args = argparse.Namespace(seed=2**33 + 11, seconds=0.5, trace=0)
    with open(os.devnull, "w") as err:
        return spec, harness.run(args, spec, require_chip=False,
                                 system=system, err=err)


def test_sound_run_is_correct():
    spec, out = _run()
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"cluster_s", "setup_s"}
    assert set(out["checks"]) == set(spec["config"]["limits"])


@pytest.mark.parametrize("kind", sorted(faults.CONTROLS) + sorted(faults.FAULTS))
def test_control_and_fault_are_refused(kind):
    system = dict(faults.CONTROLS, **faults.FAULTS)[kind]
    _spec, out = _run(system)
    assert not out["correct"], out["checks"]
    assert out["failed"] == out["attempted"]
