"""End-to-end multi-phase Louvain: golden results on known graphs."""

import numpy as np
import pytest

from cuvite_tpu import native
from cuvite_tpu.core.graph import Graph
from cuvite_tpu.evaluate.modularity import modularity as modularity_oracle
from cuvite_tpu.io.generate import generate_rmat
from cuvite_tpu.louvain.driver import louvain_phases, threshold_for_phase
from cuvite_tpu.utils.trace import Tracer


def test_threshold_schedule():
    assert threshold_for_phase(0) == 1e-3
    assert threshold_for_phase(3) == 1e-4
    assert threshold_for_phase(7) == 1e-5
    assert threshold_for_phase(10) == 1e-6
    assert threshold_for_phase(13) == 1e-3  # cycle wraps


def test_two_cliques_exact(two_cliques):
    res = louvain_phases(two_cliques)
    assert res.num_communities == 2
    # K5+K5+bridge: Q = 2*(10/21 - (21/42)^2) with both-direction counting
    q = modularity_oracle(two_cliques, res.communities)
    assert res.modularity == pytest.approx(q, abs=1e-5)
    assert q > 0.45


def test_karate_golden(karate):
    """Louvain on Zachary's karate club reaches Q ~ 0.40-0.42
    (the well-known value; reference uses karate.bin as its smoke test,
    /root/reference/README:53)."""
    res = louvain_phases(karate)
    q = modularity_oracle(karate, res.communities)
    assert q >= 0.38, f"karate modularity too low: {q}"
    assert 2 <= res.num_communities <= 8
    # device-reported modularity consistent with the host oracle
    assert res.modularity == pytest.approx(q, abs=1e-4)


def test_karate_sharded_runs(karate):
    res = louvain_phases(karate, nshards=8)
    q = modularity_oracle(karate, res.communities)
    assert q >= 0.38
    # Deterministic: sharded must equal single-shard exactly.
    res1 = louvain_phases(karate, nshards=1)
    np.testing.assert_array_equal(res.communities, res1.communities)


def test_threshold_cycling_converges(karate):
    res = louvain_phases(karate, threshold_cycling=True)
    q = modularity_oracle(karate, res.communities)
    assert q >= 0.38


def test_one_phase(karate):
    res = louvain_phases(karate, one_phase=True)
    assert len(res.phases) <= 1


def test_modularity_monotone_over_phases(karate):
    res = louvain_phases(karate)
    mods = [p.modularity for p in res.phases]
    assert all(b >= a - 1e-9 for a, b in zip(mods, mods[1:]))


def test_star_graph_collapses():
    """A star collapses into a single community -> Q = 0 at best."""
    n = 9
    s = np.zeros(n - 1, dtype=np.int64)
    d = np.arange(1, n, dtype=np.int64)
    g = Graph.from_edges(n, s, d)
    res = louvain_phases(g)
    assert res.num_communities <= n
    assert res.modularity <= 0.5


# ---------------------------------------------------------------------------
# CUVITE_EXCHANGE_CUTOVER (the exchange='auto' sparse cutover, env-tunable)


def test_exchange_cutover_env_override(monkeypatch):
    from cuvite_tpu.louvain.driver import (
        AUTO_SPARSE_MIN_VERTICES, exchange_cutover,
    )

    monkeypatch.delenv("CUVITE_EXCHANGE_CUTOVER", raising=False)
    assert exchange_cutover() == AUTO_SPARSE_MIN_VERTICES
    monkeypatch.setenv("CUVITE_EXCHANGE_CUTOVER", "1024")
    assert exchange_cutover() == 1024
    monkeypatch.setenv("CUVITE_EXCHANGE_CUTOVER", "0x100")
    assert exchange_cutover() == 256
    for bogus in ("zero", "-5", "0", ""):
        monkeypatch.setenv("CUVITE_EXCHANGE_CUTOVER", bogus)
        if bogus == "":
            assert exchange_cutover() == AUTO_SPARSE_MIN_VERTICES
        else:
            with pytest.warns(UserWarning, match="CUVITE_EXCHANGE_CUTOVER"):
                assert exchange_cutover() == AUTO_SPARSE_MIN_VERTICES


def test_exchange_cutover_is_honored_by_auto(karate, monkeypatch):
    """exchange='auto' on a mesh: with the cutover forced to 1 every phase
    resolves to the sparse plan (observable at the ExchangePlan.build
    chokepoint); with the default cutover (2^26) none does."""
    from cuvite_tpu.comm.exchange import ExchangePlan

    calls = []
    orig = ExchangePlan.build

    def counting(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(ExchangePlan, "build", staticmethod(counting))
    monkeypatch.setenv("CUVITE_EXCHANGE_CUTOVER", "1")
    r_sparse = louvain_phases(karate, nshards=2, exchange="auto")
    assert calls, "cutover=1 must route exchange='auto' to the sparse plan"
    n_sparse = len(calls)
    calls.clear()
    monkeypatch.delenv("CUVITE_EXCHANGE_CUTOVER")
    r_repl = louvain_phases(karate, nshards=2, exchange="auto")
    assert not calls, "below the default cutover 'auto' stays replicated"
    # Exchange choice must not change the clustering.
    np.testing.assert_array_equal(r_sparse.communities, r_repl.communities)
    assert n_sparse >= 1


# ---------------------------------------------------------------------------
# sort-engine x coloring: auto-switch to the class-capable bucketed engine


def test_sort_coloring_auto_switches_to_bucketed(karate):
    with pytest.warns(UserWarning, match="auto-switching"):
        r = louvain_phases(karate, engine="sort", coloring=4)
    r_ref = louvain_phases(karate, engine="bucketed", coloring=4)
    np.testing.assert_array_equal(r.communities, r_ref.communities)
    assert r.modularity == r_ref.modularity


def test_sort_coloring_opt_out_keeps_legacy_schedule(karate, monkeypatch):
    monkeypatch.setenv("CUVITE_KEEP_SORT_COLORING", "1")
    with pytest.warns(UserWarning, match="legacy schedule"):
        res = louvain_phases(karate, engine="sort", coloring=4)
    q = modularity_oracle(karate, res.communities)
    assert q >= 0.38


@pytest.mark.skipif(not native.available(),
                    reason="native library unavailable")
def test_evaluate_native_edges_counts_the_fused_attempts(monkeypatch):
    """A bucketed run above the native threshold: the counter sums the
    edges of every phase attempt evaluated by the fused pass, and the
    labels and reported Q equal a run without the native library."""

    class AttemptTracer(Tracer):
        def __init__(self):
            super().__init__()
            self.attempt_edges = []

        def begin_span(self, name, **attrs):
            if name == "phase":
                self.attempt_edges.append(attrs["ne"])
            return super().begin_span(name, **attrs)

    g = generate_rmat(13, edge_factor=8, seed=3)
    tr = AttemptTracer()
    res = louvain_phases(g, engine="bucketed", tracer=tr)
    assert tr.attempt_edges[0] == g.num_edges >= native.MIN_NATIVE_EDGES
    want = sum(ne for ne in tr.attempt_edges if ne >= native.MIN_NATIVE_EDGES)
    assert tr.counters["evaluate_native_edges"] == want

    monkeypatch.setenv("CUVITE_NO_NATIVE", "1")
    monkeypatch.setattr(native, "_LIB", None)
    tr0 = Tracer()
    res0 = louvain_phases(g, engine="bucketed", tracer=tr0)
    assert "evaluate_native_edges" not in tr0.counters
    assert float(res.modularity).hex() == float(res0.modularity).hex()
    np.testing.assert_array_equal(res.communities, res0.communities)
