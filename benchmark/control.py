#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (not run by the
benchmark's own runs):

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 \
        [--kinds program,control_f32,control_bf16,unchanged,...]

For each seed, at the cell's own size: the answer of each kind (the
program through the loop's own system under test, each control and each
planted fault of ``faults.py``; by default the program and the
controls), each judged by the float64 reference as a run judges it.  One
JSON line per (seed, kind) on stdout, then a summary line: per number,
the largest sound reading and the smallest reading of each other kind.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import faults, generators, harness, reference  # noqa: E402


def main(argv=None, require_chip: bool = True) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--kinds", default="program,control_f32,control_bf16")
    args = p.parse_args(argv)
    spec = harness.load_spec(args.workload)
    if require_chip:
        harness.hold_chip(spec["cell"]["chips"])
    harness.enable_cache()
    loop = harness.load_loop(spec["traffic"]["loop"])
    every = dict(program=loop.system, **faults.CONTROLS, **faults.FAULTS)
    kinds = {k: every[k] for k in args.kinds.split(",")}
    summary = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        graph = generators.make_graph(spec["config"]["generator"], seed)
        t0 = time.perf_counter()
        ref = reference.louvain(graph)
        ref_s = time.perf_counter() - t0
        for kind, fn in kinds.items():
            t0 = time.perf_counter()
            labels, q, _res = fn(graph, loop.stage_tracer())
            nums = reference.compared(
                reference.label_numbers(graph, labels, ref), q)
            nums.pop("q", None)
            print(json.dumps({"seed": seed, "kind": kind, "ref_q": ref[1],
                              "ref_s": ref_s, **nums,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
            for name, v in nums.items():
                agg = summary.setdefault(name, {})
                if kind == "program":
                    agg["sound_max"] = max(agg.get("sound_max", v), v)
                else:
                    agg[kind + "_min"] = min(agg.get(kind + "_min", v), v)
    print(json.dumps({"summary": summary, "limits":
                      spec["config"]["limits"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
