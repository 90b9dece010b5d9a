"""unstaged_idle_s: seconds per clustering in which the device was idle
and no host stage of the program was open, from the profiler trace.

The window's idle time is the complement of the device-busy intervals;
what of it lies outside the union of every ``stage.<name>`` span is the
idle time that no stage can name: the driver's own control loop between
stages.  A union, not a sum, because a stage's span may appear more than
once, nested (the benchmark's tracer and the program both annotate it).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.trace_reduce import covered, gaps, union  # noqa: E402


def unstaged_idle(reduced: dict) -> float:
    """Idle seconds of the window outside every stage span."""
    staged = union([s, e] for _n, s, e in reduced["stage_spans"])
    idle = gaps(reduced["busy_intervals"], 0.0, reduced["window_s"])
    return sum((e - s) - covered(staged, s, e) for s, e in idle)


def read(ctx):
    tr = ctx.get("trace")
    return None if tr is None else unstaged_idle(tr) / ctx["n"]
