"""The reduction from a profiler trace to device numbers, on synthetic
events and on a small trace recorded on the chip."""

import gzip
import json
import os

import pytest

from benchmark import harness, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000  # ns


def _events():
    # Window 0-100 ms; device busy 10-30 and 25-40 (overlap) and 70-80;
    # host stages: plan 0-45, iterate 45-90, coarsen 90-100.
    return {
        "host": [("bench.window", 0, 100 * MS),
                 ("stage.plan", 0, 45 * MS),
                 ("stage.upload", 1 * MS, 3 * MS),
                 ("stage.iterate", 45 * MS, 90 * MS),
                 ("stage.coarsen", 90 * MS, 100 * MS)],
        "devices": {"/device:TPU:0": [("fusion.1", 10 * MS, 30 * MS),
                                      ("fusion.2", 25 * MS, 40 * MS),
                                      ("sort.3", 70 * MS, 80 * MS),
                                      ("late", 95 * MS, 120 * MS)]},
    }


def test_busy_union_and_idle_share():
    r = trace_reduce.reduce(_events())
    assert r["window_s"] == pytest.approx(0.1)
    # 10-40 and 70-80, and 95-100 of the op that outlives the window.
    assert r["busy_s"] == pytest.approx(0.045)
    assert r["idle_share"] == pytest.approx(0.55)


def test_top_ops_clip_to_the_window():
    r = trace_reduce.reduce(_events())
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.020)
    assert ops["late"] == pytest.approx(0.005)
    assert [n for n, _ in r["device_ops"]][0] == "fusion.1"


def test_gaps_named_by_the_innermost_open_stage():
    r = trace_reduce.reduce(_events())
    gaps = r["idle_gaps"]
    # 40-70 (iterate opens at 45; the middle, 55, is in iterate),
    # 0-10 (plan), 80-95 (iterate).
    assert gaps[0][0] == "iterate" and gaps[0][1] == pytest.approx(0.030)
    assert ["iterate", pytest.approx(0.015)] in gaps
    assert ["plan", pytest.approx(0.010)] in gaps
    assert sum(g for _, g in gaps) == pytest.approx(0.055)


def test_busy_within_a_stage():
    r = trace_reduce.reduce(_events())
    assert trace_reduce.busy_within(r, "iterate") == pytest.approx(0.010)
    assert trace_reduce.busy_within(r, "plan") == pytest.approx(0.030)


def test_one_window_is_required():
    ev = _events()
    ev["host"].append(("bench.window", 0, 5))
    with pytest.raises(ValueError):
        trace_reduce.reduce(ev)


def test_peaks_refuse_an_unknown_device():
    assert trace_reduce.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        trace_reduce.peaks_for("TPU v9 imaginary")


def test_step_roofline_counts_least_bytes():
    read = harness.load_reader("step_roofline")
    mod = read.__globals__
    assert mod["least_bytes"](1000, 10) == 12 * 1000 + 20 * 10
    ctx = {"n": 1, "stage_s": {}, "trace": trace_reduce.reduce(_events()),
           "work": {"edge_iters": 819e9 * 0.001 / 12, "vertex_iters": 0.0},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    # 1 ms of least time over 10 ms busy inside iterate.
    assert read(ctx) == pytest.approx(10.0)
    assert read(dict(ctx, trace=None)) is None


def test_stage_readers_per_clustering():
    ctx = {"n": 4, "stage_s": {"plan": 2.0, "iterate": 8.0, "coarsen": 1.0},
           "trace": trace_reduce.reduce(_events())}
    assert harness.load_reader("plan_s")(ctx) == pytest.approx(0.5)
    assert harness.load_reader("iterate_s")(ctx) == pytest.approx(2.0)
    assert harness.load_reader("coarsen_s")(ctx) == pytest.approx(0.25)
    assert harness.load_reader("idle_share.cluster")(ctx) == \
        pytest.approx(55.0)


RECORDED = os.path.join(HERE, "data", "tiny_lfr_window.json.gz")


def test_recorded_chip_trace():
    """The events ``read_events`` took from a traced run on the v5e
    (the LFR-style graph of PR 22's first round, cut to 40,000 edges, a
    0.3 s window), kept as JSON."""
    with gzip.open(RECORDED, "rt") as f:
        ev = json.load(f)
    assert list(ev["devices"]) == ["/device:TPU:0"]
    r = trace_reduce.reduce(ev)
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert 0.0 <= r["idle_share"] < 1.0
    assert r["device_ops"] and len(r["idle_gaps"]) <= 10
    assert {n for n, _s, _e in r["stage_spans"]} >= {"plan", "iterate"}
    assert 0.0 < trace_reduce.busy_within(r, "iterate") <= r["busy_s"]
