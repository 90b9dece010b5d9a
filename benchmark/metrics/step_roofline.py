"""step_roofline: the phase loops' share of the chip's HBM roofline, in %.

The least time the chip could take over the window's iterations is the
least HBM traffic they need over the peak bandwidth (peaks.json).  One
Louvain iteration has to read, at the least:

- per directed edge slot (``EDGE_BYTES`` = 12): the tail's id (4 B), the
  edge weight (4 B) and the tail's current community (4 B, a gather);
- per vertex (``VERTEX_BYTES`` = 20): its own community (4 B), its
  weighted degree (4 B), its community's total degree (4 B), and write
  its new community (4 B) and its share of the new community totals (4 B).

Counted over the real (not padded) edges and vertices of every phase
attempt, times its iterations; the operations are a few per edge, so the
bound is bandwidth.  The time is the device-busy time inside the
driver's ``iterate`` stage spans, from the profiler trace.  Whatever
implements the step, the same work is counted.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.trace_reduce import busy_within  # noqa: E402

EDGE_BYTES = 12
VERTEX_BYTES = 20


def least_bytes(edge_iters: float, vertex_iters: float) -> float:
    """Least HBM bytes of the iterations: edge slots x iterations and
    vertices x iterations, each summed over the phase attempts."""
    return EDGE_BYTES * edge_iters + VERTEX_BYTES * vertex_iters


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    busy = busy_within(tr, "iterate")
    if busy <= 0.0:
        return None
    need = least_bytes(ctx["work"]["edge_iters"], ctx["work"]["vertex_iters"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / busy
