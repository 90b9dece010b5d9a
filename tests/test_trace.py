"""Tracer / diagnostics tests."""

import os

import numpy as np
import pytest

from cuvite_tpu.louvain.driver import louvain_phases
from cuvite_tpu.utils.trace import NullTracer, Tracer, rss_high_water_mb


def test_tracer_stages_and_counters():
    tr = Tracer()
    with tr.stage("load"):
        pass
    with tr.stage("iterate"):
        pass
    with tr.stage("iterate"):
        pass
    tr.count("traversed_edges", 1000)
    assert tr.calls["iterate"] == 2
    assert tr.counters["traversed_edges"] == 1000
    rep = tr.report()
    assert "iterate" in rep and "TEPS" in rep and "rss high-water" in rep


def test_null_tracer_is_free():
    tr = NullTracer()
    with tr.stage("x"):
        pass
    tr.count("y")
    assert tr.times == {} and tr.counters == {}


def test_rss_positive():
    assert rss_high_water_mb() > 1.0


def test_driver_fills_tracer(karate):
    for engine in ("bucketed", "fused"):
        tr = Tracer()
        res = louvain_phases(karate, engine=engine, tracer=tr)
        assert res.modularity > 0.40
        assert tr.times.get("iterate", 0) > 0
        assert tr.counters["traversed_edges"] >= karate.num_edges
        assert tr.teps() > 0


def test_cli_trace_flag(tmp_path, karate, capsys):
    from cuvite_tpu.cli import main
    from cuvite_tpu.io.vite import write_vite

    p = str(tmp_path / "k.bin")
    write_vite(p, karate)
    main(["--file", p, "--bits64", "--trace", "--quiet"])
    out = capsys.readouterr().out
    assert "stage breakdown" in out and "TEPS" in out


def test_dist_stats_report(karate):
    from cuvite_tpu.core.distgraph import DistGraph
    from cuvite_tpu.utils.trace import dist_stats_report

    dg = DistGraph.build(karate, 4)
    rep = dist_stats_report(dg, ghost_counts=[3, 1, 2, 0])
    assert f"Number of vertices: {karate.num_vertices}" in rep
    assert f"Number of edges: {karate.num_edges}" in rep
    assert "Standard deviation:" in rep
    assert "Ghost vertices per shard: max 3" in rep
    counts = [sh.n_real_edges for sh in dg.shards]
    assert f"Maximum number of edges: {max(counts)}" in rep


def test_shard_diag_files(tmp_path, karate):
    """--diag-prefix writes one file per shard, a line per phase (the
    reference's dat.out.<rank>, main.cpp:101-110)."""
    prefix = str(tmp_path / "diag" / "dat.out")
    # exchange='sparse' explicitly: ghost counts only exist on the sparse
    # plan, and 'auto' routes a karate-sized graph to the replicated
    # exchange (no ghost plan to report).
    res = louvain_phases(karate, nshards=4, diag_prefix=prefix,
                         exchange="sparse")
    assert res.modularity > 0.40
    for s in range(4):
        lines = open(f"{prefix}.{s}").read().splitlines()
        # One line per phase ATTEMPT: the final no-gain phase writes its
        # line too but is not appended to res.phases.
        assert len(lines) >= len(res.phases)
        assert lines[0].startswith("phase 0: owned=")
        assert "ghosts=" in lines[0] and "Q=" in lines[0]


def test_shard_diag_lazy_file_creation(tmp_path):
    """No file exists until the first write for that shard (a 64-shard
    run that only diagnoses shard 3 creates ONE file)."""
    from cuvite_tpu.utils.trace import ShardDiag

    prefix = str(tmp_path / "sub" / "dat.out")
    with ShardDiag(prefix, nshards=4) as diag:
        assert not os.path.exists(os.path.dirname(prefix))
        diag.write(2, "hello")
        assert os.path.exists(f"{prefix}.2")
        assert not os.path.exists(f"{prefix}.0")
        assert not os.path.exists(f"{prefix}.1")


def test_shard_diag_truncates_on_reopen(tmp_path):
    """A rerun with the same prefix REPLACES each shard file (the
    reference's per-rank ofstreams truncate too): stale lines from a
    previous run never mix into a fresh diagnosis."""
    from cuvite_tpu.utils.trace import ShardDiag

    prefix = str(tmp_path / "dat.out")
    with ShardDiag(prefix, nshards=2) as diag:
        diag.write(0, "old run line 1")
        diag.write(0, "old run line 2")
        diag.write(1, "old shard-1 line")
    with ShardDiag(prefix, nshards=2) as diag:
        diag.write(0, "new run line")
        # Shard 1 never written this run: its file keeps the OLD content
        # (truncation is per-file on first write, not a prefix sweep).
    assert open(f"{prefix}.0").read().splitlines() == ["new run line"]
    assert open(f"{prefix}.1").read().splitlines() == ["old shard-1 line"]


def test_tracer_stage_reentrancy():
    """Nested stage() of the SAME name: the outer window CONTAINS the
    inner one, so the accumulated time double-counts the inner span by
    design (calls tells the reader how many windows there were), and a
    recorder sees properly nested spans."""
    import time

    from cuvite_tpu.obs import FlightRecorder, spans_of, validate_trace

    with FlightRecorder() as rec:
        tr = Tracer(recorder=rec)
        with tr.stage("iterate"):
            with tr.stage("iterate"):
                time.sleep(0.002)
    assert tr.calls["iterate"] == 2
    assert tr.times["iterate"] >= 2 * 0.002  # outer contains inner
    assert validate_trace(rec.records) == []
    spans = spans_of(rec.records, "iterate")
    assert len(spans) == 2
    outer = next(s for s in spans if s["begin"]["parent"] is None)
    inner = next(s for s in spans if s is not outer)
    assert inner["begin"]["parent"] == outer["id"]


def test_breakdown_keeps_sub_millisecond_stages():
    """ISSUE 6 satellite: breakdown() must NOT round — the historical
    round(v, 3) reported a 0.4 ms upload as 0.0, making real-vs-absent
    indistinguishable to the regression gate.  report() still rounds for
    humans."""
    tr = Tracer()
    tr.times["upload"] = 4.2e-4
    tr.times["iterate"] = 1.23456789
    tr.calls = {"upload": 1, "iterate": 1}
    bd = tr.breakdown()
    assert bd["upload_s"] == 4.2e-4      # full precision survives
    assert bd["iterate_s"] == 1.23456789
    assert bd["coarsen_s"] == 0.0        # canonical stages always present


def test_cli_dist_stats_flag(tmp_path, karate, capsys):
    from cuvite_tpu.cli import main
    from cuvite_tpu.io.vite import write_vite

    p = str(tmp_path / "k.bin")
    write_vite(p, karate)
    main(["--file", p, "--bits64", "--dist-stats", "--shards", "2",
          "--quiet"])
    out = capsys.readouterr().out
    assert "Graph edge distribution characteristics" in out


class _CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each
    name as it opens and closes."""

    def __init__(self, log, name):
        self.log, self.name = log, name

    def __enter__(self):
        self.log.append(("open", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("close", self.name))
        return False


def _count_annotations(monkeypatch):
    import jax.profiler

    log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: _CountingAnnotation(log, name))
    return log


def test_stage_opens_one_profiler_annotation(monkeypatch):
    log = _count_annotations(monkeypatch)
    with Tracer().stage("x"):
        assert log == [("open", "stage.x")]
    assert log == [("open", "stage.x"), ("close", "stage.x")]
    log.clear()
    with NullTracer().stage("x"):
        pass
    assert log == []


def test_stage_annotation_closes_on_exception(monkeypatch):
    log = _count_annotations(monkeypatch)
    tr = Tracer()
    try:
        with tr.stage("x"):
            raise RuntimeError("boom")
    except RuntimeError:
        pass
    assert log == [("open", "stage.x"), ("close", "stage.x")]
    assert tr.calls["x"] == 1


def test_envelope_span_is_an_unprefixed_annotation(monkeypatch):
    """begin_span/end_span open and close one annotation named after the
    span (no ``stage.`` prefix), on the normal and the exception path,
    with and without a flight recorder; a disabled tracer opens none."""
    from cuvite_tpu.obs import FlightRecorder, spans_of, validate_trace

    log = _count_annotations(monkeypatch)
    with FlightRecorder() as rec:
        for tr in (Tracer(), Tracer(recorder=rec)):
            h = tr.begin_span("phase", index=0)
            with tr.stage("plan"):
                pass
            tr.end_span(h, gained=True)
            try:
                h = tr.begin_span("pack")
                raise ValueError("mid-span")
            except ValueError:
                pass
            finally:
                tr.end_span(h, error="mid-span")
    assert log == [("open", "phase"), ("open", "stage.plan"),
                   ("close", "stage.plan"), ("close", "phase"),
                   ("open", "pack"), ("close", "pack")] * 2
    assert validate_trace(rec.records) == []
    assert len(spans_of(rec.records, "phase")) == 1
    assert len(spans_of(rec.records, "pack")) == 1
    log.clear()
    tr = NullTracer()
    tr.end_span(tr.begin_span("phase"))
    assert log == []


def test_plan_build_leaf_stages(karate):
    """The per-phase plan stage holds the partition (DistGraph.build) and
    the host degree bucketing as stages of their own, strictly inside it;
    tracing leaves the labels as they are."""
    from cuvite_tpu.obs import FlightRecorder, spans_of, validate_trace

    with FlightRecorder() as rec:
        tr = Tracer(recorder=rec)
        res = louvain_phases(karate, engine="bucketed", tracer=tr)
    plain = louvain_phases(karate, engine="bucketed")
    np.testing.assert_array_equal(res.communities, plain.communities)
    assert res.modularity == plain.modularity
    assert validate_trace(rec.records) == []
    plans = {s["id"]: s for s in spans_of(rec.records, "plan")}
    for leaf in ("partition", "bucket"):
        spans = spans_of(rec.records, leaf)
        assert spans, leaf
        for s in spans:
            parent = plans[s["begin"]["parent"]]
            assert s["end"]["dur_s"] < parent["end"]["dur_s"], leaf
    assert tr.times["partition"] + tr.times["bucket"] < tr.times["plan"]


SCOPES = ("cuvite.tables", "cuvite.heavy", "cuvite.assemble",
          "cuvite.select", "cuvite.modularity")


@pytest.mark.parametrize("engine", ["bucketed", "pallas"])
def test_phase_loop_carries_named_scopes(karate, engine):
    """The lowered bucketed phase loop names each part of the step: the
    community tables, one scope per degree class of the plan (XLA rows
    and Pallas kernel classes alike), the heavy residual, assembly,
    select and the modularity pass."""
    import re

    from cuvite_tpu.core.distgraph import DistGraph
    from cuvite_tpu.louvain.driver import (
        MAX_TOTAL_ITERATIONS, PhaseRunner, _run_phase_loop)

    dg = DistGraph.build(karate, 1, min_nv_pad=4096, min_ne_pad=16384)
    runner = PhaseRunner(dg, engine=engine)
    wdt = np.dtype(runner.constant.dtype)
    text = _run_phase_loop.lower(
        runner._extra, runner.comm0, np.asarray(1e-6, dtype=wdt),
        np.asarray(-1.0, dtype=wdt), call=runner._call,
        max_iters=MAX_TOTAL_ITERATIONS).as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in text, scope
    # Kernel classes are stored transposed, [width, rows].
    widths = [d.shape[0] if d.shape[1] == len(v) else d.shape[1]
              for v, d, _w in runner._bucket_extra[0]]
    assert len(widths) >= 2
    assert {int(w) for w in re.findall(r"cuvite\.class_w(\d+)", text)} \
        == set(widths)
