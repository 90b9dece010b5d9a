"""Correctness of the jitted Louvain step against an independent oracle.

The oracle re-implements, with plain Python dicts, the per-vertex semantics of
distExecuteLouvainIteration / distGetMaxIndex
(/root/reference/louvain.cpp:2185-2382): gain formula, strictly-positive-gain
moves, tie-break to the smaller community id, and the singleton-swap guard.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from cuvite_tpu.comm.mesh import make_mesh, shard_1d
from cuvite_tpu.core.distgraph import DistGraph
from cuvite_tpu.core.graph import Graph
from cuvite_tpu.evaluate.modularity import modularity as modularity_oracle
from cuvite_tpu.louvain.step import make_single_step, make_sharded_step
from cuvite_tpu.comm.mesh import VERTEX_AXIS


def oracle_step(graph: Graph, comm: np.ndarray):
    """One synchronous sweep; returns (target, modularity_of_input)."""
    nv = graph.num_vertices
    vdeg = graph.weighted_degrees().astype(np.float64)
    two_m = graph.total_edge_weight_twice()
    const = 1.0 / two_m
    comm_deg = np.zeros(nv)
    comm_size = np.zeros(nv, dtype=np.int64)
    for v in range(nv):
        comm_deg[comm[v]] += vdeg[v]
        comm_size[comm[v]] += 1

    target = comm.copy()
    le_xx = 0.0
    for v in range(nv):
        e0, e1 = graph.offsets[v], graph.offsets[v + 1]
        if e0 == e1:
            continue
        weights_to = {}
        self_loop = 0.0
        for k in range(e0, e1):
            t = int(graph.tails[k])
            w = float(graph.weights[k])
            if t == v:
                self_loop += w
            c = int(comm[t])
            weights_to[c] = weights_to.get(c, 0.0) + w
        cc = int(comm[v])
        counter0 = weights_to.get(cc, 0.0)
        le_xx += counter0
        eix = counter0 - self_loop
        ax = comm_deg[cc] - vdeg[v]
        max_gain, max_idx, max_size = 0.0, cc, comm_size[cc]
        for c, eiy in weights_to.items():
            if c == cc:
                continue
            ay = comm_deg[c]
            gain = 2.0 * (eiy - eix) - 2.0 * vdeg[v] * (ay - ax) * const
            if gain > max_gain or (
                gain == max_gain and gain != 0.0 and c < max_idx
            ):
                max_gain, max_idx, max_size = gain, c, comm_size[c]
        if max_size == 1 and comm_size[cc] == 1 and max_idx > cc:
            max_idx = cc
        target[v] = max_idx
    q = le_xx * const - np.square(comm_deg * const).sum()
    return target, q


def run_device_step(graph: Graph, comm: np.ndarray, nshards: int = 1):
    dg = DistGraph.build(graph, nshards)
    src, dst, w = dg.stacked_edges()
    vdeg = dg.padded_weighted_degrees()
    nvt = dg.total_padded_vertices
    comm_pad = np.arange(nvt, dtype=dg.graph.policy.vertex_dtype)
    comm_pad[dg.old_to_pad] = dg.old_to_pad[comm]  # labels in padded space
    const = jnp.asarray(
        1.0 / graph.total_edge_weight_twice(), dtype=graph.policy.weight_dtype
    )
    if nshards == 1:
        step = make_single_step(nvt)
        t, q, n, _ = step(src, dst, w, comm_pad, vdeg, const)
    else:
        mesh = make_mesh(nshards)
        step = make_sharded_step(mesh, VERTEX_AXIS, nvt)
        t, q, n, _ = step(
            shard_1d(mesh, src), shard_1d(mesh, dst), shard_1d(mesh, w),
            shard_1d(mesh, comm_pad), shard_1d(mesh, vdeg), const,
        )
    t = np.asarray(t)
    # back to original-id labels
    target_old = dg.pad_to_old[t[dg.old_to_pad]]
    return target_old, float(q), int(n)


@pytest.mark.parametrize("fixture", ["karate", "two_cliques", "ring8"])
def test_step_matches_oracle(fixture, request):
    graph = request.getfixturevalue(fixture)
    comm = np.arange(graph.num_vertices, dtype=np.int64)
    for it in range(4):
        expected, q_exp = oracle_step(graph, comm)
        got, q_got, _ = run_device_step(graph, comm)
        np.testing.assert_array_equal(
            got, expected, err_msg=f"iteration {it} targets diverge"
        )
        assert q_got == pytest.approx(q_exp, abs=1e-5)
        comm = expected


def test_modularity_identity_assignment(karate):
    """Identity assignment: e_in = self-loops (none) -> Q = -sum (k_i/2m)^2."""
    comm = np.arange(karate.num_vertices, dtype=np.int64)
    _, q, _ = run_device_step(karate, comm)
    assert q == pytest.approx(modularity_oracle(karate, comm), abs=1e-6)


@pytest.mark.parametrize("nshards", [2, 4, 8])
def test_sharded_step_matches_single(karate, nshards):
    comm = np.arange(karate.num_vertices, dtype=np.int64)
    for it in range(3):
        t1, q1, n1 = run_device_step(karate, comm, nshards=1)
        tn, qn, nn = run_device_step(karate, comm, nshards=nshards)
        np.testing.assert_array_equal(t1, tn)
        assert qn == pytest.approx(q1, abs=1e-5)
        assert nn == n1
        comm = t1


def test_first_step_two_cliques(two_cliques):
    """After convergence each K5 collapses to one community."""
    comm = np.arange(10, dtype=np.int64)
    for _ in range(6):
        comm, _ = oracle_step(two_cliques, comm)
    assert len(set(comm[:5])) == 1
    assert len(set(comm[5:])) == 1
    assert comm[0] != comm[5]


def test_packed_sort_debug_bounds_guard(monkeypatch):
    """CUVITE_DEBUG_BOUNDS=1 turns packed-key bound violations into hard
    errors instead of silent key corruption (advisor r2 finding).

    The env var is read once at module import (advisor r3: a trace-time
    read could never take effect after the step cache warms), so the test
    toggles the module attribute directly."""
    import jax.numpy as jnp

    from cuvite_tpu.ops import segment
    from cuvite_tpu.ops.segment import sort_edges_by_vertex_comm

    src = jnp.array([0, 1, 2], dtype=jnp.int32)
    ckey = jnp.array([0, 1, 9], dtype=jnp.int32)  # >= key_bound
    w = jnp.ones(3, dtype=jnp.float32)
    monkeypatch.setattr(segment, "DEBUG_BOUNDS", True)
    with pytest.raises(AssertionError, match="bound violation"):
        sort_edges_by_vertex_comm(src, ckey, w, src_bound=4, key_bound=4)
    # In-bounds input passes and round-trips exactly.
    out = sort_edges_by_vertex_comm(
        src, jnp.array([2, 1, 0], dtype=jnp.int32), w,
        src_bound=4, key_bound=4)
    assert [int(x) for x in out[0]] == [0, 1, 2]
    assert [int(x) for x in out[1]] == [2, 1, 0]


def run_bucketed_step(graph: Graph, comm: np.ndarray):
    """One bucketed_step sweep over the default degree-class ladder
    (degree classes, the sorted heavy residual, the assembly perm)."""
    import functools

    import jax

    from cuvite_tpu.louvain.bucketed import (
        BucketPlan,
        bucketed_step,
        build_assemble_perm,
    )

    dg = DistGraph.build(graph, 1)
    sh = dg.shards[0]
    plan = BucketPlan.build(np.asarray(sh.src), np.asarray(sh.dst),
                            np.asarray(sh.w), nv_local=dg.nv_pad, base=0)
    vdt, wdt = np.int32, np.float32
    buckets = tuple((jnp.asarray(b.verts.astype(vdt)),
                     jnp.asarray(b.dst.astype(vdt)),
                     jnp.asarray(b.w.astype(wdt))) for b in plan.buckets)
    heavy = tuple(jnp.asarray(a.astype(t)) for a, t in zip(
        (plan.heavy_src, plan.heavy_dst, plan.heavy_w), (vdt, vdt, wdt)))
    perm = build_assemble_perm([b.verts for b in plan.buckets], dg.nv_pad)
    nvt = dg.total_padded_vertices
    comm_pad = np.arange(nvt, dtype=vdt)
    comm_pad[dg.old_to_pad] = dg.old_to_pad[comm]
    step = jax.jit(functools.partial(bucketed_step, nv_total=nvt,
                                     sentinel=np.iinfo(vdt).max))
    t, q, _n, _ = step(
        buckets, heavy, jnp.asarray(plan.self_loop.astype(wdt)),
        jnp.asarray(comm_pad),
        jnp.asarray(dg.padded_weighted_degrees().astype(wdt)),
        jnp.asarray(1.0 / graph.total_edge_weight_twice(), dtype=wdt),
        assemble_perm=jnp.asarray(perm))
    t = np.asarray(t)
    return dg.pad_to_old[t[dg.old_to_pad]], float(q), plan.has_heavy


@pytest.mark.parametrize("hub_degree", [8192, 8193, 16384])
def test_bucketed_hub_at_heavy_class_boundary_matches_oracle(hub_degree):
    """A hub at the top degree class (8192), one past it (8193: the
    sorted heavy residual) and twice it: every sweep's targets equal the
    dict-based oracle's."""
    rng = np.random.default_rng(hub_degree)
    nv = 20000
    hub_dst = rng.choice(np.arange(1, nv), size=hub_degree, replace=False)
    bg = rng.integers(1, nv, (2, 12000))
    graph = Graph.from_edges(
        nv, np.concatenate([np.zeros(hub_degree, np.int64), bg[0]]),
        np.concatenate([hub_dst, bg[1]]))
    assert int(graph.degrees()[0]) == hub_degree
    comm = np.arange(nv, dtype=np.int64)
    for it in range(3):
        expected, q_exp = oracle_step(graph, comm)
        got, q_got, has_heavy = run_bucketed_step(graph, comm)
        assert has_heavy == (hub_degree > 8192)
        np.testing.assert_array_equal(
            got, expected, err_msg=f"iteration {it} targets diverge")
        assert q_got == pytest.approx(q_exp, abs=1e-5)
        comm = expected
