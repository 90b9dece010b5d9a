"""The readers of the program's stages (evaluate, partition, bucket) and
of the idle time outside every stage, on synthetic traces; and every
reader's value when each stage span appears twice, nested, as it does
when both the benchmark's tracer and the program annotate a stage."""

import gzip
import json
import os

import pytest

from benchmark import harness, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000  # ns
US = 1_000      # ns

STAGES = [("stage.plan", 0, 30 * MS),
          ("stage.partition", 2 * MS, 10 * MS),
          ("stage.bucket", 12 * MS, 20 * MS),
          ("stage.upload", 21 * MS, 23 * MS),
          ("stage.iterate", 30 * MS, 60 * MS),
          ("stage.evaluate", 62 * MS, 70 * MS),
          ("stage.coarsen", 72 * MS, 90 * MS)]


def _events(doubled_by=None):
    """Window 0-100 ms.  Device busy 10-15 (in plan), 35-55 (iterate),
    80-85 (coarsen).  No stage is open at 60-62, 70-72 and 90-100: 14 ms
    of idle time that no stage names.  ``doubled_by`` adds to each stage
    an outer copy of itself, that many ns wider at each end."""
    host = [("bench.window", 0, 100 * MS)] + list(STAGES)
    if doubled_by is not None:
        host += [(n, s - doubled_by, e + doubled_by) for n, s, e in STAGES]
    return {"host": host,
            "devices": {"/device:TPU:0": [("fusion.1", 10 * MS, 15 * MS),
                                          ("fusion.2", 35 * MS, 55 * MS),
                                          ("sort.3", 80 * MS, 85 * MS)]}}


def _ctx(events, n=2):
    return {"n": n,
            "stage_s": {"plan": 0.060, "partition": 0.016, "bucket": 0.016,
                        "upload": 0.004, "iterate": 0.060,
                        "evaluate": 0.016, "coarsen": 0.036},
            "trace": trace_reduce.reduce(events),
            "work": {"edge_iters": 819e9 * 0.001 / 12, "vertex_iters": 0.0},
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_stage_readers_per_clustering():
    ctx = _ctx(_events())
    assert harness.load_reader("evaluate_s")(ctx) == pytest.approx(0.008)
    assert harness.load_reader("partition_s")(ctx) == pytest.approx(0.008)
    assert harness.load_reader("bucket_s")(ctx) == pytest.approx(0.008)


@pytest.mark.parametrize("name", ["evaluate_s", "partition_s", "bucket_s"])
def test_stage_reader_is_silent_where_the_stage_never_ran(name):
    """A program without the stage (an older parent) gives no value."""
    ctx = _ctx(_events())
    del ctx["stage_s"][name[:-2]]
    assert harness.load_reader(name)(ctx) is None


def test_unstaged_idle_per_clustering():
    read = harness.load_reader("unstaged_idle_s")
    ctx = _ctx(_events())
    assert read(ctx) == pytest.approx(0.014 / 2)
    assert read(dict(ctx, trace=None)) is None


def test_unstaged_idle_is_idle_time_only():
    """Time outside every stage in which the device was busy is not
    counted: here 90-100 ms turns busy."""
    ev = _events()
    ev["devices"]["/device:TPU:0"].append(("fusion.9", 90 * MS, 100 * MS))
    read = harness.load_reader("unstaged_idle_s")
    assert read(_ctx(ev, n=1)) == pytest.approx(0.004)


READERS = ["plan_s", "iterate_s", "coarsen_s", "step_roofline",
           "idle_share.cluster", "evaluate_s", "partition_s", "bucket_s",
           "unstaged_idle_s"]


@pytest.mark.parametrize("doubled_by", [0, 1 * US])
@pytest.mark.parametrize("name", READERS)
def test_doubled_stage_spans_read_the_same(name, doubled_by):
    read = harness.load_reader(name)
    once = read(_ctx(_events()))
    twice = read(_ctx(_events(doubled_by)))
    assert once is not None
    # Outer copies 1 us wider move the stage boundaries by at most 1 us
    # each: six boundaries face unstaged idle time here.
    assert twice == pytest.approx(once, abs=1e-5)


@pytest.mark.parametrize("doubled_by", [0, 1 * US])
def test_doubled_stage_spans_name_the_same_gaps(doubled_by):
    once = trace_reduce.reduce(_events())["idle_gaps"]
    twice = trace_reduce.reduce(_events(doubled_by))["idle_gaps"]
    assert [n for n, _ in twice] == [n for n, _ in once]


def test_unstaged_idle_on_the_recorded_chip_trace():
    """On the events of a traced run on the v5e (test_trace_reduce.py's
    recording), the unstaged idle time is a part of the idle time."""
    with gzip.open(os.path.join(HERE, "data", "tiny_lfr_window.json.gz"),
                   "rt") as f:
        r = trace_reduce.reduce(json.load(f))
    read = harness.load_reader("unstaged_idle_s")
    v = read({"n": 1, "trace": r})
    assert 0.0 <= v <= r["window_s"] - r["busy_s"] + 1e-12
