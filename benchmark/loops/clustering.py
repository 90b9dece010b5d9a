"""The ``clustering`` loop: whole clusterings back to back, one at a time.

A traffic mix (``traffic/<mix>.json``) whose ``loop`` is ``clustering``
runs this.  One client, closed loop: a clustering of the cell's graph,
host CSR to host labels, from scratch each time; another starts while
the window is shorter than its seconds, and the last one finishes.  The
harness calls, in order:

- ``setup(graph, mix, seed)``: the state the window drives (the graph);
- ``window(state, seconds, system)``: one window (``seconds`` 0 is the
  warm-up: one whole clustering);
- ``judge(state, out, limits, err)``: after the window, every answer
  against the plain reference (``reference.py``).

``system(graph, tracer)`` is the system under test; the faults and the
controls (``faults.py``) stand in its place.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from benchmark import reference


def stage_tracer():
    """The program's Tracer, each stage also a profiler span
    (``stage.<name>``) so the trace can name what the host was doing."""
    import jax
    from cuvite_tpu.utils.trace import Tracer

    class AnnotatedTracer(Tracer):
        @contextlib.contextmanager
        def stage(self, name):
            with jax.profiler.TraceAnnotation("stage." + name):
                with super().stage(name):
                    yield

    return AnnotatedTracer()


def system(graph, tracer):
    """One whole clustering with the driver's defaults, host graph to
    host labels.  Returns (labels, reported Q, the result)."""
    from cuvite_tpu import Graph, louvain_phases

    g = Graph(offsets=graph.offsets, tails=graph.tails,
              weights=graph.weights)
    res = louvain_phases(g, tracer=tracer)
    return np.asarray(res.communities), float(res.modularity), res


def phase_work(res, edge_iters: float) -> dict:
    """Edge and vertex slots swept, summed over every phase attempt.
    ``edge_iters`` is the Tracer's count of traversed edges, which
    covers every attempt.  The attempt that did not gain is not in
    ``res.phases``: its iterations are the rest of ``total_iterations``
    and its vertices the final communities."""
    gained_it = sum(p.iterations for p in res.phases)
    last_it = res.total_iterations - gained_it
    nc = int(res.communities.max()) + 1 if len(res.communities) else 0
    return {
        "edge_iters": float(edge_iters),
        "vertex_iters": float(sum(p.num_vertices * p.iterations
                                  for p in res.phases) + nc * last_it),
    }


def setup(graph, mix: dict, seed: int):
    return graph


def window(graph, seconds: float, system=system) -> dict:
    tracer = stage_tracer()
    answers, work = [], {"edge_iters": 0.0, "vertex_iters": 0.0}
    t0 = time.perf_counter()
    while not answers or time.perf_counter() - t0 < seconds:
        before = tracer.counters.get("traversed_edges", 0.0)
        labels, q, res = system(graph, tracer)
        answers.append((labels, q))
        w = phase_work(res, tracer.counters.get("traversed_edges", 0.0)
                       - before)
        work["edge_iters"] += w["edge_iters"]
        work["vertex_iters"] += w["vertex_iters"]
    elapsed = time.perf_counter() - t0
    return {
        "answers": answers,
        "end_to_end": {"cluster_s": elapsed / len(answers)},
        # What the per-layer readers (metrics/) take.
        "layers": {"n": len(answers), "stage_s": dict(tracer.times),
                   "work": work},
        "note": (f"{len(answers)} clusterings in {elapsed:.3f} s; TEPS "
                 f"{work['edge_iters'] / elapsed:.6g}"),
    }


def judge(graph, out: dict, limits: dict, err) -> tuple:
    """(answers failed, {number: worst value and its limit}).  The
    reference's own answer once, then the numbers of every answer
    (computed once per distinct labelling)."""
    t_ref = time.perf_counter()
    ref_answer = reference.louvain(graph)
    worst, failed, seen = {}, 0, []
    for labels, q in out["answers"]:
        nums = next((r for lab, r in seen if np.array_equal(lab, labels)),
                    None)
        if nums is None:
            nums = reference.label_numbers(graph, labels, ref_answer)
            seen.append((labels, nums))
        numbers = reference.compared(nums, q)
        if reference.judge(numbers, limits):
            failed += 1
        for name in limits:
            v = numbers.get(name, float("inf"))
            worst[name] = max(worst.get(name, v), v)
    q_vals = [r["q"] for _l, r in seen if "q" in r]
    print(f"reference: Q {ref_answer[1]!r}, {time.perf_counter() - t_ref:.3f}"
          f" s; answers: {len(seen)} distinct, Q {q_vals}", file=err)
    return failed, {name: {"value": worst[name], "limit": limits[name]}
                    for name in limits}
