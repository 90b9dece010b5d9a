"""Workload subsystem tests: converter round-trips (SNAP / MTX / METIS,
32- and 64-bit), synthesizer determinism + the golden-envelope gate, the
registry's offline fallback, and the bench harness's compile guard
(which must ABORT, emitting nothing, when a timed run recompiles).
"""

import gzip
import json
import os
import time

import numpy as np
import pytest

from cuvite_tpu.core.graph import Graph
from cuvite_tpu.core.types import default_policy, wide_policy
from cuvite_tpu.io.vite import read_vite, write_vite
from cuvite_tpu.workloads.convert import convert, edges_to_vite
from cuvite_tpu.workloads.synth import synthesize

# A small weighted graph with GAPS in the id space (relabel exercised)
# and no duplicate edges, so Graph.from_edges is a bit-exact oracle for
# the converter's canonical (row-sorted) output.
EDGES = [(1, 4, 0.5), (1, 7, 2.0), (4, 7, 1.5), (7, 13, 1.0),
         (13, 22, 0.25), (4, 22, 3.0), (22, 31, 1.25), (1, 31, 0.75)]
IDS = sorted({v for e in EDGES for v in e[:2]})
REMAP = {v: i for i, v in enumerate(IDS)}


def expected_graph(policy, weights=True):
    src = np.array([REMAP[u] for u, v, w in EDGES])
    dst = np.array([REMAP[v] for u, v, w in EDGES])
    w = np.array([w for u, v, w in EDGES]) if weights else None
    return Graph.from_edges(len(IDS), src, dst, weights=w, policy=policy)


def assert_csr_equal(got: Graph, exp: Graph):
    assert np.array_equal(got.offsets, exp.offsets)
    assert np.array_equal(got.tails, exp.tails)
    assert np.array_equal(got.weights, exp.weights)


@pytest.mark.parametrize("bits64", [False, True], ids=["32bit", "64bit"])
def test_snap_roundtrip_bit_equality(tmp_path, bits64):
    path = tmp_path / "g.txt"
    lines = ["# SNAP-style comment"]
    lines += [f"{u}\t{v}\t{w}" for u, v, w in EDGES]
    path.write_text("\n".join(lines) + "\n")
    out = str(tmp_path / "g.vite")
    stats = convert(str(path), out, fmt="snap", bits64=bits64)
    assert stats.relabeled and stats.num_vertices == len(IDS)
    assert stats.num_edges == 2 * len(EDGES)
    policy = wide_policy() if bits64 else default_policy()
    g = read_vite(out, bits64=bits64)
    assert_csr_equal(g, expected_graph(policy))
    # write_vite of the read-back graph reproduces the file byte-for-byte
    # (converter output is io/vite.py-compatible, not merely readable).
    out2 = str(tmp_path / "g2.vite")
    write_vite(out2, g, bits64=bits64)
    assert open(out, "rb").read() == open(out2, "rb").read()


def test_snap_gz_output_is_byte_identical(tmp_path):
    plain = tmp_path / "g.txt"
    plain.write_text("\n".join(f"{u} {v} {w}" for u, v, w in EDGES) + "\n")
    gzp = tmp_path / "g.txt.gz"
    with gzip.open(gzp, "wb") as f:
        f.write(plain.read_bytes())
    convert(str(plain), str(tmp_path / "a.vite"), fmt="snap")
    convert(str(gzp), str(tmp_path / "b.vite"), fmt="snap")
    assert (tmp_path / "a.vite").read_bytes() \
        == (tmp_path / "b.vite").read_bytes()


@pytest.mark.parametrize("bits64", [False, True], ids=["32bit", "64bit"])
def test_mtx_symmetric_roundtrip(tmp_path, bits64):
    # 1-based dense ids, lower-triangle storage, real field.
    n = len(IDS)
    path = tmp_path / "g.mtx"
    lines = ["%%MatrixMarket matrix coordinate real symmetric",
             "% comment", f"{n} {n} {len(EDGES)}"]
    for u, v, w in EDGES:
        i, j = REMAP[u] + 1, REMAP[v] + 1
        lines.append(f"{max(i, j)} {min(i, j)} {w}")
    path.write_text("\n".join(lines) + "\n")
    out = str(tmp_path / "g.vite")
    stats = convert(str(path), out, fmt="mtx", bits64=bits64)
    assert not stats.relabeled and stats.symmetrized
    policy = wide_policy() if bits64 else default_policy()
    assert_csr_equal(read_vite(out, bits64=bits64), expected_graph(policy))


def test_mtx_general_not_symmetrized(tmp_path):
    # 'general' adjacency already lists both directions: converting must
    # NOT double it.
    n = len(IDS)
    both = [(REMAP[u], REMAP[v], w) for u, v, w in EDGES]
    both += [(v, u, w) for u, v, w in both]
    path = tmp_path / "g.mtx"
    lines = ["%%MatrixMarket matrix coordinate real general",
             f"{n} {n} {len(both)}"]
    lines += [f"{i + 1} {j + 1} {w}" for i, j, w in both]
    path.write_text("\n".join(lines) + "\n")
    out = str(tmp_path / "g.vite")
    stats = convert(str(path), out, fmt="mtx")
    assert not stats.symmetrized and stats.num_edges == len(both)
    assert_csr_equal(read_vite(out, bits64=False),
                     expected_graph(default_policy()))


@pytest.mark.parametrize("bits64", [False, True], ids=["32bit", "64bit"])
def test_metis_roundtrip_with_edge_weights(tmp_path, bits64):
    # METIS fmt=001 (edge weights), 1-based, both directions listed,
    # one isolated vertex appended (blank adjacency line).
    n = len(IDS)
    adj = [[] for _ in range(n + 1)]
    for u, v, w in EDGES:
        adj[REMAP[u]].append((REMAP[v] + 1, w))
        adj[REMAP[v]].append((REMAP[u] + 1, w))
    lines = ["% comment", f"{n + 1} {len(EDGES)} 001"]
    for nbrs in adj:
        lines.append(" ".join(f"{t} {w:g}" for t, w in nbrs))
    (tmp_path / "g.graph").write_text("\n".join(lines) + "\n")
    out = str(tmp_path / "g.vite")
    stats = convert(str(tmp_path / "g.graph"), out, bits64=bits64)
    assert stats.fmt == "metis" and not stats.symmetrized
    assert stats.num_vertices == n + 1  # the isolated vertex survives
    policy = wide_policy() if bits64 else default_policy()
    g = read_vite(out, bits64=bits64)
    exp = expected_graph(policy)
    assert np.array_equal(g.offsets[: n + 1], exp.offsets)
    assert int(g.offsets[n + 1]) == int(exp.offsets[n])  # degree-0 tail
    assert np.array_equal(g.tails, exp.tails)
    assert np.array_equal(g.weights, exp.weights)


def test_metis_unweighted(tmp_path):
    n = len(IDS)
    adj = [[] for _ in range(n)]
    for u, v, _ in EDGES:
        adj[REMAP[u]].append(REMAP[v] + 1)
        adj[REMAP[v]].append(REMAP[u] + 1)
    lines = [f"{n} {len(EDGES)}"]
    lines += [" ".join(str(t) for t in nbrs) for nbrs in adj]
    (tmp_path / "g.metis").write_text("\n".join(lines) + "\n")
    out = str(tmp_path / "g.vite")
    convert(str(tmp_path / "g.metis"), out)
    assert_csr_equal(read_vite(out, bits64=False),
                     expected_graph(default_policy(), weights=False))


def test_metis_parse_spans_text_blocks(tmp_path):
    """A METIS file larger than one reader block must parse identically:
    the block-final newline is a boundary artifact, NOT an isolated
    vertex's blank adjacency line (regression: block-size-dependent
    'more adjacency lines than nv' / silently shifted adjacency)."""
    from cuvite_tpu.workloads.convert import metis_edge_chunks

    n = len(IDS)
    adj = [[] for _ in range(n)]
    for u, v, _ in EDGES:
        adj[REMAP[u]].append(REMAP[v] + 1)
        adj[REMAP[v]].append(REMAP[u] + 1)
    lines = [f"{n} {len(EDGES)}"]
    lines += [" ".join(str(t) for t in nbrs) for nbrs in adj]
    path = tmp_path / "g.graph"
    path.write_text("\n".join(lines) + "\n")

    def collect(block_bytes):
        chunks = list(metis_edge_chunks(str(path), block_bytes=block_bytes))
        s = np.concatenate([c[0] for c in chunks])
        d = np.concatenate([c[1] for c in chunks])
        return s, d

    s_big, d_big = collect(8 << 20)
    s_tiny, d_tiny = collect(4)  # every line its own block
    assert np.array_equal(s_big, s_tiny)
    assert np.array_equal(d_big, d_tiny)


def test_chunking_does_not_change_output(tmp_path):
    """The same edge stream through 1-edge chunks and one big chunk must
    produce byte-identical files (the canonicalization pass's job)."""
    src = np.array([REMAP[u] for u, v, w in EDGES])
    dst = np.array([REMAP[v] for u, v, w in EDGES])
    w = np.array([w for u, v, w in EDGES])
    one = [(src, dst, w)]
    tiny = [(src[i:i + 1], dst[i:i + 1], w[i:i + 1])
            for i in np.random.default_rng(0).permutation(len(src))]
    a, b = str(tmp_path / "a.vite"), str(tmp_path / "b.vite")
    edges_to_vite(iter(one), a, num_vertices=len(IDS), relabel="none")
    edges_to_vite(iter(tiny), b, num_vertices=len(IDS), relabel="none",
                  chunk_edges=2)
    assert open(a, "rb").read() == open(b, "rb").read()


# ---------------------------------------------------------------------------
# Synthesizer + golden envelope (the tier-1 verify-golden run)

SYNTH_EDGES = 40_000
SYNTH_SEED = 7


@pytest.fixture(scope="module")
def synth_workload(tmp_path_factory):
    d = tmp_path_factory.mktemp("synth")
    out = str(d / "pl.vite")
    payload = synthesize(out, edges=SYNTH_EDGES, seed=SYNTH_SEED)
    return out, payload


def test_synth_is_deterministic(tmp_path, synth_workload):
    _, payload = synth_workload
    p2 = synthesize(str(tmp_path / "pl2.vite"), edges=SYNTH_EDGES,
                    seed=SYNTH_SEED)
    assert p2["sha256"] == payload["sha256"]
    assert p2["result"]["num_edges"] == payload["result"]["num_edges"]
    # A different seed must actually change the graph.
    p3 = synthesize(str(tmp_path / "pl3.vite"), edges=SYNTH_EDGES,
                    seed=SYNTH_SEED + 1)
    assert p3["sha256"] != payload["sha256"]


def test_synth_provenance_and_truth(synth_workload):
    out, payload = synth_workload
    assert payload["source"] == "synthesized"
    assert os.path.exists(out + ".provenance.json")
    assert os.path.exists(payload["truth_path"])
    ne = payload["result"]["num_edges"]
    assert 0.9 * SYNTH_EDGES <= ne <= SYNTH_EDGES  # self-draws dropped


def test_synth_golden_envelope_verify(synth_workload):
    """End-to-end golden gate on the synthesized power-law graph: the
    checked-in envelope (workloads/golden.json, powerlaw-test/default)
    must admit a fresh clustering run, F-score included."""
    from cuvite_tpu.louvain.driver import louvain_phases
    from cuvite_tpu.workloads.golden import measure_run, verify

    out, payload = synth_workload
    g = read_vite(out, bits64=False)
    res = louvain_phases(g, verbose=False)
    measured = measure_run(res.communities, res,
                           truth_path=payload["truth_path"],
                           provenance="synthesized")
    ok, problems = verify("powerlaw-test", "default", measured)
    assert ok, problems
    assert measured["f_score"] > 0.85  # planted structure is recovered


def test_golden_envelope_catches_regression(tmp_path, synth_workload):
    from cuvite_tpu.workloads.golden import (
        envelope_from_measurement, check_envelope,
    )

    measured = {"modularity": 0.69, "phases": 2, "communities": 23,
                "f_score": 0.92}
    entry = envelope_from_measurement(measured)
    assert check_envelope(entry, measured) == []
    worse = dict(measured, modularity=0.60)
    assert any("Q=" in p for p in check_envelope(entry, worse))
    split = dict(measured, communities=230)
    assert any("communities" in p for p in check_envelope(entry, split))
    bad_f = dict(measured, f_score=0.5)
    assert any("f_score" in p for p in check_envelope(entry, bad_f))
    # A better-than-golden F-score never fails (one-sided).
    better = dict(measured, f_score=0.99)
    assert not any("f_score" in p for p in check_envelope(entry, better))


def test_verify_golden_missing_entry_fails(synth_workload, tmp_path):
    from cuvite_tpu.workloads.golden import verify

    measured = {"modularity": 0.5, "phases": 2, "communities": 10}
    ok, problems = verify("no-such-dataset", "default", measured,
                          path=str(tmp_path / "empty.json"))
    assert not ok and "no golden entry" in problems[0]


def test_workloads_cli_synth_convert_verify(tmp_path):
    """The CLI wiring end-to-end, in-process: synth -> verify-golden
    --update-golden -> verify-golden (pass)."""
    from cuvite_tpu.workloads.__main__ import main

    out = str(tmp_path / "cli.vite")
    golden = str(tmp_path / "golden.json")
    assert main(["synth", "--edges", "20000", "--seed", "11",
                 "--out", out]) == 0
    assert main(["verify-golden", "--dataset", "cli-test", "--file", out,
                 "--golden", golden, "--update-golden"]) == 0
    assert main(["verify-golden", "--dataset", "cli-test", "--file", out,
                 "--golden", golden]) == 0
    data = json.load(open(golden))
    assert "cli-test/default" in data["entries"]


# ---------------------------------------------------------------------------
# Registry: offline fallback (no network on this rig)


def test_registry_offline_fallback(tmp_path, monkeypatch):
    import cuvite_tpu.workloads.registry as reg

    fake = reg.Dataset(
        name="fake-tiny", url="http://127.0.0.1:9/nothing.txt.gz",
        fmt="snap", num_vertices=1000, num_edges_undirected=10_000,
        synth_edges=20_000)
    monkeypatch.setitem(reg.DATASETS, "fake-tiny", fake)
    payload = reg.fetch("fake-tiny", str(tmp_path), timeout=2)
    assert payload["source"] == "offline-synthesized"
    assert payload["stands_in_for"] == "fake-tiny"
    out = str(tmp_path / "fake-tiny.vite")
    g = read_vite(out, bits64=False)
    assert g.num_edges == payload["result"]["num_edges"]
    prov = reg.load_provenance(out)
    assert prov["source"] == "offline-synthesized"
    assert "fetch_error" in prov


def test_registry_no_offline_fallback_raises(tmp_path, monkeypatch):
    import cuvite_tpu.workloads.registry as reg

    fake = reg.Dataset(
        name="fake-tiny2", url="http://127.0.0.1:9/nothing.txt.gz",
        fmt="snap", num_vertices=10, num_edges_undirected=10)
    monkeypatch.setitem(reg.DATASETS, "fake-tiny2", fake)
    with pytest.raises(Exception):
        reg.fetch("fake-tiny2", str(tmp_path), offline_fallback=False,
                  timeout=2)


# ---------------------------------------------------------------------------
# Bench harness: record schema + THE compile-guard abort


def test_bench_record_schema_and_guard_pass():
    from cuvite_tpu.io.generate import generate_rmat
    from cuvite_tpu.workloads.bench import run_bench, validate_record

    # edge_factor=10 is used NOWHERE else in the suite: the cold-run
    # asserts below (compile_events non-empty, guard checked) need this
    # graph's compiled programs to be absent from the in-process jit
    # cache, and a shared shape lets an earlier test warm them (the
    # bucketed plan geometry collapses to the same pow2 ladder for
    # same-(scale, edge_factor) rmats — same idiom as test_obs.py's
    # shape-unique graph).
    g = generate_rmat(9, edge_factor=10, seed=3)
    # t_start pinned HERE: the default anchors at bench-module import,
    # and this test runs near the end of a long tier-1 — the suite's
    # elapsed wall must not eat the budget (the budget path has its own
    # assertions; this test targets the guarded steady-state path).
    rec = run_bench(g, repeats=2, budget_s=600, platform="cpu",
                    graph_label="rmat9", scale=9,
                    t_start=time.perf_counter())
    assert validate_record(rec) == []
    assert rec["compile_guard"] == {"checked": True, "new_compiles": 0}
    assert rec["runs"] == 2 and len(rec["teps_runs"]) == 2
    assert rec["platform"] == "cpu" and rec["value"] > 0
    # Schema v2: per-stage breakdown of the recorded run (ISSUE 3;
    # coalesce_s — the device relabel+coalesce slice — since ISSUE 8).
    for k in ("coarsen_s", "coalesce_s", "upload_s", "iterate_s"):
        assert k in rec["stages"] and rec["stages"][k] >= 0
    assert rec["stages"]["iterate_s"] > 0  # the phase loops always run
    # Schema v5 (ISSUE 20: optional `mix` block; v4 added the ISSUE-6
    # self-describing telemetry fields asserted below).
    assert rec["schema"] == 5
    assert rec["convergence_summary"], "recorded run must carry digests"
    assert all(d["iterations"] >= 1 for d in rec["convergence_summary"])
    # The warm-up compiles under the recorder: cold cost is on record.
    assert rec["compile_events"], "cold run must record compile events"
    assert all("module" in e for e in rec["compile_events"])
    assert isinstance(rec["hbm_peak_by_buffer"], dict)


def test_bench_aborts_on_injected_recompile():
    """Inject a recompile into the first timed run (the warm-up sees a
    DIFFERENT graph shape) and assert the harness refuses to produce a
    record — the acceptance gate for VERDICT r5 weak #6."""
    from cuvite_tpu.io.generate import generate_rmat
    from cuvite_tpu.workloads.bench import (
        BenchCompileGuardError, run_bench,
    )

    # Suite-unique edge_factor=10 shapes (see the schema test above):
    # the injected SECOND shape must be guaranteed-cold in the
    # in-process jit cache, or the guard legitimately sees zero fresh
    # compiles and this test misfires on suite order.
    shapes = iter([generate_rmat(9, edge_factor=10, seed=3),
                   generate_rmat(8, edge_factor=10, seed=4)])
    with pytest.raises(BenchCompileGuardError) as exc:
        run_bench(lambda: next(shapes), repeats=1, budget_s=600,
                  platform="cpu", graph_label="sabotage",
                  t_start=time.perf_counter())
    assert exc.value.compile_log  # the abort carries the compile list


def test_bench_main_emits_no_json_on_guard_trip(monkeypatch, capsys):
    import cuvite_tpu.workloads.bench as wb

    def boom(*a, **k):
        raise wb.BenchCompileGuardError(["Compiling sabotage"])

    monkeypatch.setattr(wb, "run_bench", boom)
    rc = wb.main(["--scale", "6", "--repeats", "1"])
    out = capsys.readouterr().out
    assert rc == 3
    assert not out.strip(), f"guard trip must emit NO json, got: {out!r}"


def test_bench_record_names_the_device_it_ran_on(monkeypatch, capsys):
    """No fallback: the bench runs on JAX's default backend and stamps
    platform, device_kind and device_count into its record."""
    import json

    import jax

    import cuvite_tpu.workloads.bench as wb

    rec = {"metric": "louvain_teps_per_chip", "value": 1.0,
           "unit": "traversed_edges/sec", "vs_baseline": 0.1,
           "platform": "cpu", "graph": "x", "modularity": 0.1,
           "phases": 1, "compile_guard": {"checked": True,
                                          "new_compiles": 0},
           "stages": {"coarsen_s": 0.0, "coalesce_s": 0.0,
                      "rebin_s": 0.0, "upload_s": 0.0, "iterate_s": 1.0},
           "engine": "bucketed", "schema": 4,
           "convergence_summary": [{"phase": 0, "iterations": 3}],
           "compile_events": [], "hbm_peak_by_buffer": {"slab": 1024}}
    monkeypatch.setattr(wb, "run_bench", lambda *a, **k: dict(rec))
    assert wb.main(["--scale", "6", "--repeats", "1"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    dev = jax.devices()
    assert out["platform"] == dev[0].platform
    assert out["device_kind"] == dev[0].device_kind
    assert out["device_count"] == len(dev)


def test_validate_record_rejects_unchecked_nonzero_compiles():
    from cuvite_tpu.workloads.bench import validate_record

    rec = {"metric": "louvain_teps_per_chip", "value": 1.0,
           "unit": "traversed_edges/sec", "vs_baseline": 0.1,
           "platform": "cpu", "graph": "x", "modularity": 0.1,
           "phases": 1, "compile_guard": {"checked": True,
                                          "new_compiles": 2},
           "stages": {"coarsen_s": 0.0, "coalesce_s": 0.0,
                      "rebin_s": 0.0, "upload_s": 0.0, "iterate_s": 1.0},
           "engine": "bucketed", "schema": 4,
           "convergence_summary": [{"phase": 0, "iterations": 3}],
           "compile_events": [{"module": "jit(f)", "dur_s": 0.5}],
           "hbm_peak_by_buffer": {"slab": 1024}}
    assert any("new_compiles" in p for p in validate_record(rec))
    # Schema v2: a record without the stage breakdown (or with a bogus
    # one) is rejected.
    old = dict(rec, compile_guard={"checked": True, "new_compiles": 0})
    del old["stages"]
    assert any("stages" in p for p in validate_record(old))
    bad = dict(rec, compile_guard={"checked": True, "new_compiles": 0},
               stages={"coarsen_s": -1.0, "upload_s": 0.0,
                       "iterate_s": 1.0})
    assert any("coarsen_s" in p for p in validate_record(bad))
    # ISSUE 8: coalesce_s is a required stage key.
    noco = dict(rec, compile_guard={"checked": True, "new_compiles": 0},
                stages={"coarsen_s": 0.0, "upload_s": 0.0,
                        "iterate_s": 1.0})
    assert any("coalesce_s" in p for p in validate_record(noco))
    # Schema v3: an engine-less record is rejected, and a pallas record
    # must carry the kernel-coverage fields (honest TEPS labeling).
    ok = dict(rec, compile_guard={"checked": True, "new_compiles": 0})
    noeng = dict(ok)
    del noeng["engine"]
    assert any("engine" in p for p in validate_record(noeng))
    pal = dict(ok, engine="pallas")
    assert any("pallas_coverage" in p for p in validate_record(pal))
    assert any("pallas_width_hits" in p for p in validate_record(pal))
    pal_ok = dict(pal, pallas_coverage=0.93,
                  pallas_width_hits={"8": 1000, "32": 500})
    assert validate_record(pal_ok) == []
    pal_bad = dict(pal_ok, pallas_coverage=1.7)
    assert any("pallas_coverage" in p for p in validate_record(pal_bad))
    # Schema v4: the telemetry fields are REQUIRED and type-checked; a
    # pre-v4 record (no schema field) is rejected outright.
    v3 = dict(ok)
    del v3["schema"]
    assert any("schema" in p for p in validate_record(v3))
    for key, bad_val in (("convergence_summary", "nope"),
                         ("compile_events", [{"dur_s": 1.0}]),
                         ("hbm_peak_by_buffer", [1, 2])):
        assert any(key in p for p in validate_record(dict(ok, **{key: bad_val}))), key
    # ISSUE 18: the optional exchange block — a two-level record must
    # carry its (dcn, ici) factorization and per-device table/ghost
    # bytes; a flat SPMD record carries only the mode.
    probs = validate_record(dict(ok, exchange={"mode": "twolevel"}))
    for k in ("dcn", "ici", "table_bytes_per_device", "ghost_bytes"):
        assert any(k in p for p in probs), (k, probs)
    assert validate_record(dict(ok, exchange={
        "mode": "twolevel", "dcn": 2, "ici": 4,
        "table_bytes_per_device": 16384, "ghost_bytes": 6144})) == []
    assert validate_record(dict(ok, exchange={"mode": "sparse"})) == []
    assert any("mode" in p for p in validate_record(
        dict(ok, exchange={"mode": "dense"})))
    assert any("dcn" in p for p in validate_record(dict(ok, exchange={
        "mode": "twolevel", "dcn": 0, "ici": 4,
        "table_bytes_per_device": 16384, "ghost_bytes": 6144})))


# ---------------------------------------------------------------------------
# Modularity oracle size gate (VERDICT r5 weak #7)


def test_modularity_gate(karate, monkeypatch):
    from cuvite_tpu.evaluate.modularity import (
        host_oracle_max_edges, modularity, modularity_gated,
    )

    labels = np.zeros(karate.num_vertices, dtype=np.int64)
    q_oracle = modularity(karate, labels)
    q, used = modularity_gated(karate, labels, fallback=-123.0)
    assert used and q == q_oracle
    q, used = modularity_gated(karate, labels, fallback=-123.0,
                               max_edges=0)
    assert not used and q == -123.0
    monkeypatch.setenv("CUVITE_HOST_ORACLE_MAX_EDGES", "1e3")
    assert host_oracle_max_edges() == 1000
    monkeypatch.setenv("CUVITE_HOST_ORACLE_MAX_EDGES", "bogus")
    with pytest.warns(UserWarning):
        assert host_oracle_max_edges() > 0
