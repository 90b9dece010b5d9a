"""Double-single accumulation: unit tests for the ds primitives and the
1e-9-class accuracy gate for per-phase modularity (VERDICT round-1 item 4;
analog of the reference's double accumulation, louvain.cpp:2433-2481)."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from cuvite_tpu import native
from cuvite_tpu.coarsen.rebuild import coarsen_graph, renumber_communities
from cuvite_tpu.core.distgraph import DistGraph
from cuvite_tpu.core.graph import Graph
from cuvite_tpu.evaluate.modularity import modularity as host_mod
from cuvite_tpu.io.generate import generate_rmat
from cuvite_tpu.louvain.driver import louvain_phases
from cuvite_tpu.louvain.precise import phase_modularity
from cuvite_tpu.ops import exactsum as ds
from cuvite_tpu.utils.trace import Tracer
from cuvite_tpu.workloads.synth import lfr_edges

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native library unavailable")


def test_ds_tree_sum_beats_f32():
    """Adversarial mix of magnitudes: ds must track the f64 oracle far
    beyond f32's 2^-24."""
    rng = np.random.default_rng(0)
    x = np.concatenate([
        rng.uniform(1e6, 1e7, 4096),
        rng.uniform(1e-3, 1e-2, 4096),
    ]).astype(np.float32)
    rng.shuffle(x)
    hi, lo = ds.ds_tree_sum(jnp.asarray(x))
    got = ds.ds_to_f64((hi, lo))
    want = float(np.sum(x.astype(np.float64)))
    f32 = float(np.sum(x))
    assert abs(got - want) <= 1e-9 * abs(want)
    assert abs(got - want) < abs(f32 - want)  # strictly better than f32


def test_ds_mul_exactness():
    a, b = np.float32(16777217.0 / 16.0), np.float32(3.0000001)
    hi, lo = ds.ds_mul(ds.ds_from_f32(jnp.float32(a)),
                       ds.ds_from_f32(jnp.float32(b)))
    want = float(np.float64(a) * np.float64(b))
    assert abs(ds.ds_to_f64((hi, lo)) - want) <= 1e-14 * abs(want)


def test_ds_segment_sums_sorted_matches_f64():
    rng = np.random.default_rng(1)
    keys = np.sort(rng.integers(0, 50, 8192)).astype(np.int32)
    vals = rng.uniform(1e-3, 1e5, 8192).astype(np.float32)
    run_hi, run_lo, last = ds.ds_segment_sums_sorted(
        jnp.asarray(keys), jnp.asarray(vals))
    run_hi, run_lo, last = map(np.asarray, (run_hi, run_lo, last))
    want = np.zeros(50)
    np.add.at(want, keys, vals.astype(np.float64))
    got = {}
    for i in np.nonzero(last)[0]:
        got[keys[i]] = np.float64(run_hi[i]) + np.float64(run_lo[i])
    for k, w in got.items():
        assert abs(w - want[k]) <= 1e-9 * max(abs(want[k]), 1.0)


@pytest.mark.parametrize(
    "scale", [16, pytest.param(20, marks=pytest.mark.slow)])
def test_phase_modularity_matches_f64_oracle(scale):
    """Device ds modularity vs host f64 oracle within 1e-9*|Q| — scale-20
    R-MAT with f32 (unit) weights is the VERDICT acceptance case."""
    g = generate_rmat(scale, edge_factor=16, seed=1)
    dg = DistGraph.build(g, 1)
    # Non-trivial synthetic assignment with big skewed communities: maps
    # every vertex to one of ~1000 communities (padded space).
    rng = np.random.default_rng(2)
    labels = rng.integers(0, 1000, g.num_vertices)
    comm_pad = np.arange(dg.total_padded_vertices, dtype=np.int64)
    comm_pad[dg.old_to_pad] = dg.old_to_pad[labels]
    got = phase_modularity(dg, comm_pad)
    want = host_mod(g, labels)
    assert abs(got - want) <= 1e-9 * abs(want), (got, want)


def test_reported_modularity_is_precise_end_to_end():
    g = generate_rmat(13, edge_factor=8, seed=3)
    res = louvain_phases(g, engine="bucketed")
    want = host_mod(g, res.communities)
    assert abs(res.modularity - want) <= 1e-9 * abs(want)


def test_multishard_reported_modularity_is_precise():
    g = generate_rmat(11, edge_factor=8, seed=4)
    res = louvain_phases(g, nshards=4)
    want = host_mod(g, res.communities)
    assert abs(res.modularity - want) <= 1e-9 * abs(want)


# -- the fused native pass of the host branch --------------------------------


def _unit_graph(kind):
    """Integer-weight graphs: unit R-MAT, unit LFR (n 2000, the paper's
    parameters), and a coarsened R-MAT with self-loops and weights > 1."""
    if kind == "rmat":
        return generate_rmat(12, edge_factor=8, seed=5)
    if kind == "lfr":
        nv, src, dst, _ = lfr_edges(2000, 2.0, 1.0, 20, 50, 20, 100, 0.4,
                                    seed=3)
        return Graph.from_edges(nv, src, dst)
    g = generate_rmat(12, edge_factor=8, seed=6)
    rng = np.random.default_rng(7)
    dense, nc = renumber_communities(rng.integers(0, 300, g.num_vertices))
    return coarsen_graph(g, dense, nc)


def _labels(kind, dg, seed=0):
    """Padded-space labels: every vertex alone, all in one community, or a
    few padded ids with gaps between them, the last padded id among them."""
    comm_pad = np.arange(dg.total_padded_vertices, dtype=np.int64)
    if kind == "one":
        comm_pad[dg.old_to_pad] = 0
    elif kind == "gaps":
        rng = np.random.default_rng(seed)
        top = dg.total_padded_vertices - 1
        ids = np.unique(np.append(rng.choice(top, 40, replace=False), top))
        lab = rng.choice(ids, dg.graph.num_vertices)
        lab[0] = top
        comm_pad[dg.old_to_pad] = lab
    return comm_pad


def _fused(monkeypatch, dg, comm_pad):
    """phase_modularity through the fused pass whatever the graph's size;
    asserts it engaged and counted the graph's edges."""
    monkeypatch.setattr(native, "MIN_NATIVE_EDGES", 1)
    tr = Tracer()
    got = phase_modularity(dg, comm_pad, tracer=tr)
    assert tr.counters["evaluate_native_edges"] == dg.graph.num_edges
    return got


@needs_native
@pytest.mark.parametrize("labels", ["singletons", "one", "gaps"])
@pytest.mark.parametrize("graph", ["rmat", "lfr", "coarse"])
def test_native_phase_modularity_bit_identical_integer_weights(
        monkeypatch, graph, labels):
    """Integer weights make every sum exact in any order: the fused pass
    gives the numpy oracle's Q to the last bit."""
    g = _unit_graph(graph)
    if graph == "coarse":
        assert (g.tails == g.sources()).any() and g.weights.max() > 1
    dg = DistGraph.build(g, 1)
    comm_pad = _labels(labels, dg)
    got = _fused(monkeypatch, dg, comm_pad)
    want = host_mod(g, comm_pad[dg.old_to_pad])
    assert float(got).hex() == float(want).hex(), (got, want)


@needs_native
def test_native_phase_modularity_sharded_layout(monkeypatch):
    """Four shards: the labels go through old_to_pad, not a prefix view."""
    g = generate_rmat(11, edge_factor=8, seed=8)
    dg = DistGraph.build(g, 4)
    comm_pad = _labels("gaps", dg, seed=9)
    got = _fused(monkeypatch, dg, comm_pad)
    want = host_mod(g, comm_pad[dg.old_to_pad])
    assert float(got).hex() == float(want).hex(), (got, want)


@needs_native
@pytest.mark.parametrize("labels", ["singletons", "one", "gaps"])
def test_native_phase_modularity_float_weights(monkeypatch, labels):
    """Non-integer f32 weights: the sums round in another order than the
    oracle's, so the two agree to f64 rounding."""
    rng = np.random.default_rng(10)
    nv, ne = 3000, 40000
    g = Graph.from_edges(nv, rng.integers(0, nv, ne), rng.integers(0, nv, ne),
                         weights=rng.random(ne))
    assert g.weights.dtype == np.float32
    dg = DistGraph.build(g, 1)
    comm_pad = _labels(labels, dg, seed=11)
    got = _fused(monkeypatch, dg, comm_pad)
    want = host_mod(g, comm_pad[dg.old_to_pad])
    assert abs(got - want) <= 1e-13, (got, want)


_TERMS_SCRIPT = """
import hashlib
import numpy as np
from cuvite_tpu import native
rng = np.random.default_rng(12)
nv, ne = 20000, 400000
offsets = np.zeros(nv + 1, dtype=np.int64)
offsets[1:] = np.cumsum(np.bincount(rng.integers(0, nv, ne), minlength=nv))
tails = rng.integers(0, nv, ne).astype(np.int32)
w = rng.random(ne).astype(np.float32)
comm = rng.integers(0, 700, nv).astype(np.int32)
e_in, two_m, a_c = native.modularity_terms(offsets, tails, w, comm, 700)
print(float(e_in).hex(), float(two_m).hex(),
      hashlib.sha256(a_c.tobytes()).hexdigest())
"""


@needs_native
def test_native_modularity_terms_same_bits_any_thread_count():
    """Fixed row blocks, added in block order: 1 and 4 OpenMP threads give
    the same bits, non-integer weights included."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    outs = []
    for threads in ("1", "4"):
        env = dict(os.environ, OMP_NUM_THREADS=threads, JAX_PLATFORMS="cpu",
                   PYTHONPATH=root)
        env.pop("CUVITE_NO_NATIVE", None)
        r = subprocess.run([sys.executable, "-c", _TERMS_SCRIPT], env=env,
                           cwd=root, capture_output=True, text=True,
                           timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        outs.append(r.stdout.split())
    assert len(outs[0]) == 3 and outs[0] == outs[1], outs


@pytest.mark.parametrize("why", ["no_native", "below_threshold"])
def test_phase_modularity_numpy_fallback(monkeypatch, why):
    """Under CUVITE_NO_NATIVE, or below MIN_NATIVE_EDGES, the host branch
    is the numpy oracle itself and counts nothing."""
    if why == "no_native":
        g = generate_rmat(13, edge_factor=8, seed=13)
        assert g.num_edges >= native.MIN_NATIVE_EDGES
        monkeypatch.setenv("CUVITE_NO_NATIVE", "1")
        monkeypatch.setattr(native, "_LIB", None)
    else:
        g = generate_rmat(10, edge_factor=8, seed=13)
        assert g.num_edges < native.MIN_NATIVE_EDGES

    def refuse(*a, **k):
        raise AssertionError("the fused pass ran")

    monkeypatch.setattr(native, "modularity_terms", refuse)
    dg = DistGraph.build(g, 1)
    comm_pad = _labels("gaps", dg, seed=14)
    tr = Tracer()
    got = phase_modularity(dg, comm_pad, tracer=tr)
    assert "evaluate_native_edges" not in tr.counters
    assert got == host_mod(g, comm_pad[dg.old_to_pad])
