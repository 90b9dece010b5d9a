"""Reduce a profiler trace of the measured window to device numbers.

The window's trace is one ``.xplane.pb`` read with
``jax.profiler.ProfileData``.  All its planes share one time base, so the
host spans the benchmark writes with ``jax.profiler.TraceAnnotation``
(``bench.window`` around the window, ``stage.<name>`` around each stage
of the program's Tracer) sit on the same clock as the device's ops.

- busy: the union of the intervals in which an op ran on the device
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped to
  the window and averaged over the devices;
- idle share: 1 - busy / window;
- top ops: device seconds summed by op name, leaving out the ops that
  hold others (``while``, ``conditional``, ``call``), whose time is
  their body ops';
- idle gaps: the stretches of the window with no device op, each named
  by the innermost host stage open at its middle ("host" if none).
"""

from __future__ import annotations

import glob
import json
import os
import re

WINDOW = "bench.window"
STAGE_PREFIX = "stage."
OPS_LINE = "XLA Ops"
CONTAINERS = ("(while)", "(conditional)", "(call)")
DEVICE_PREFIX = "/device:TPU:"
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks_for(device_kind: str, path: str = PEAKS) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    with open(path, encoding="utf-8") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; add its published numbers")
    return table[device_kind]


def union(intervals) -> list:
    """Merge [start, end) intervals; returns them sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def covered(intervals, lo: float, hi: float) -> float:
    """Length of [lo, hi) covered by disjoint ``intervals``."""
    return sum(e - s for s, e in clip(intervals, lo, hi))


def gaps(intervals, lo: float, hi: float) -> list:
    """The complement of disjoint sorted ``intervals`` inside [lo, hi)."""
    out, t = [], lo
    for s, e in clip(intervals, lo, hi):
        if s > t:
            out.append([t, s])
        t = max(t, e)
    if hi > t:
        out.append([t, hi])
    return out


_HLO = re.compile(r"^%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")


def op_name(hlo: str) -> str:
    """A device op's short name: ``while.106 (while)`` from its HLO text."""
    m = _HLO.match(hlo)
    return f"{m.group(1)} ({m.group(2)})" if m else hlo[:80]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_events(path: str) -> dict:
    """Plain lists from the xplane: device ops per device, host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [(op_name(ev.name), ev.start_ns,
                    ev.start_ns + ev.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                     for line in plane.lines for ev in line.events
                     if ev.name == WINDOW or ev.name.startswith(STAGE_PREFIX)]
    return {"devices": devices, "host": host}


def reduce(events: dict, top: int = 10) -> dict:
    """Window, busy and idle seconds, top ops, named gaps and the host
    stage spans (seconds from the window's start)."""
    windows = [(s, e) for name, s, e in events["host"] if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    lo, hi = windows[0]
    window_s = (hi - lo) * 1e-9
    stages = sorted((name[len(STAGE_PREFIX):], s, e)
                    for name, s, e in events["host"]
                    if name.startswith(STAGE_PREFIX) and e > lo and s < hi)
    busy_by_dev, op_time, busy_sets = [], {}, []
    for ops in events["devices"].values():
        merged = union([s, e] for _n, s, e in ops)
        busy_sets.append(merged)
        busy_by_dev.append(covered(merged, lo, hi) * 1e-9)
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0 and not name.endswith(CONTAINERS):
                op_time[name] = op_time.get(name, 0.0) + d * 1e-9
    if not busy_sets:
        raise ValueError("no device ops in the trace")
    busy_s = sum(busy_by_dev) / len(busy_by_dev)
    # Gaps of the first device; with one chip per cell that is the chip.
    named = []
    for s, e in gaps(busy_sets[0], lo, hi):
        mid = 0.5 * (s + e)
        open_ = [st for st in stages if st[1] <= mid < st[2]]
        label = (min(open_, key=lambda st: st[2] - st[1])[0]
                 if open_ else "host")
        named.append([label, (e - s) * 1e-9])
    named.sort(key=lambda x: -x[1])
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": [[n, t] for n, t in ops_top],
        "idle_gaps": named[:top],
        "stage_spans": [(n, (s - lo) * 1e-9, (e - lo) * 1e-9)
                        for n, s, e in stages],
        "busy_intervals": [[(s - lo) * 1e-9, (e - lo) * 1e-9]
                           for s, e in clip(busy_sets[0], lo, hi)],
    }


def busy_within(reduced: dict, stage: str) -> float:
    """Device-busy seconds of the first device inside the host spans of
    one stage (the spans of one stage never nest in each other)."""
    spans = union([s, e] for n, s, e in reduced["stage_spans"] if n == stage)
    return sum(covered(reduced["busy_intervals"], s, e) for s, e in spans)
