"""The benchmark harness: one cell, one run, one result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The harness is driven by data and holds only what every cell shares.  It
finds the cell in ``BENCHMARK.json``, its configuration in
``configs/<config>.json`` (the generator and its parameters, and the
limits of the comparison), its traffic mix in ``traffic/<mix>.json``
(the parameters, and the name of the loop that reads them), the loop in
``loops/<loop>.py`` (the window, the system under test, the comparison
against the reference, the end-to-end values) and each per-layer
metric's reader in ``metrics/<metric>.py``.  A new mix of a loop is a
data file; a new kind of traffic is a loop file and a data file.  A run:

1. holds the chip (a TPU, or the run fails with no result), and keeps
   JAX's compile cache at a fixed path in the checkout;
2. makes the graph from the configuration and ``--seed``, and the loop's
   state from the graph, the mix and ``--seed``;
3. warms up with one whole unit of the traffic, which compiles (or loads
   from the cache) every program the window will run;
4. runs the window, profiled with ``--trace 1``; a compile or a cache
   load inside it fails the run;
5. reads the device's peak memory, and has the loop judge every answer
   of the window against the plain reference;
6. prints the compared numbers with their limits on stderr, and the
   result line, last, on stdout.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")

# Events that mean an executable was made: a compile, or a load from the
# persistent cache.  Neither may happen inside the window.
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


class ChipMissing(RuntimeError):
    pass


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_spec(workload: str, root: str = ROOT) -> dict:
    """Everything one cell needs, found by name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {
        "cell": cell,
        "config": load_json(os.path.join(root, config["file"])),
        "traffic": load_json(os.path.join(HERE, "traffic",
                                          cell["traffic"] + ".json")),
        "end_to_end": [m for m in bench["end_to_end"]
                       if _applies(m, workload)],
        "per_layer": [m for m in bench["per_layer"] if _applies(m, workload)],
    }


def load_reader(name: str):
    """``metrics/<name>.py``'s ``read(ctx)``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def hold_chip(chips: int):
    """The devices of this cell; anything but enough TPUs is an error."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise ChipMissing(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise ChipMissing(f"the cell needs {chips} chips, JAX sees "
                          f"{len(devs)}")
    return devs[:chips]


def enable_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class ExecutableCounter:
    """Counts compiles and cache loads while armed (jax.monitoring)."""

    def __init__(self):
        self.compiles = 0
        self.loads = 0
        self.compile_s = 0.0

    def __call__(self, event, duration, **_kw):
        if event == _COMPILE:
            self.compiles += 1
            self.compile_s += duration
        elif event == _CACHE_LOAD:
            self.loads += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)
        return False


def load_loop(name: str):
    """``loops/<name>.py``: a traffic mix's loop, found by the name its
    data file gives."""
    return importlib.import_module(f"benchmark.loops.{name}")


def memory_peak(devs) -> int | None:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run(args, spec: dict, *, require_chip: bool = True, system=None,
        t_process: float | None = None, err=sys.stderr) -> dict:
    """One run of one cell; returns the result line's object.  ``system``
    stands in for the loop's system under test (the tests' faults)."""
    import jax

    from benchmark import generators, trace_reduce

    t0 = time.perf_counter() if t_process is None else t_process
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    if require_chip:
        devs = hold_chip(cell["chips"])
    else:
        devs = jax.devices()[:cell["chips"]]
    import cuvite_tpu  # noqa: F401  (the system under test: fail early)

    enable_cache()
    loop = load_loop(traffic["loop"])
    system = system or loop.system

    t_gen = time.perf_counter()
    graph = generators.make_graph(config["generator"], args.seed)
    state = loop.setup(graph, traffic, args.seed)
    print(f"graph: {graph.num_vertices} vertices, {graph.num_edges} "
          f"directed edges, made in {time.perf_counter() - t_gen:.3f} s",
          file=err, flush=True)

    with ExecutableCounter() as warm:
        t_warm = time.perf_counter()
        loop.window(state, 0.0, system)
    print(f"warm-up: {time.perf_counter() - t_warm:.3f} s, {warm.compiles} "
          f"compiles ({warm.compile_s:.3f} s), {warm.loads} cache loads",
          file=err, flush=True)

    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    with ExecutableCounter() as window, \
            jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        setup_s = time.perf_counter() - t0
        out = loop.window(state, args.seconds, system)
    if args.trace:
        jax.profiler.stop_trace()
    if window.compiles or window.loads:
        raise RuntimeError(
            f"{window.compiles} compiles and {window.loads} cache loads "
            "inside the window: the warm-up missed a shape")
    peak = memory_peak(devs)
    print(f"window: {out['note']}; device peak {peak} bytes", file=err,
          flush=True)

    failed, checks = loop.judge(state, out, config["limits"], err)
    result = {"correct": failed == 0, "attempted": len(out["answers"]),
              "failed": failed}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if args.trace:
        reduced = trace_reduce.reduce(trace_reduce.read_events(
            trace_reduce.find_xplane(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        ctx = dict(out["layers"], trace=reduced,
                   peaks=trace_reduce.peaks_for(devs[0].device_kind))
        metrics = {}
        for m in spec["per_layer"]:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        values = dict(out["end_to_end"], setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in spec["end_to_end"]}
        result["device"] = device
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=err)
    result["checks"] = checks
    return result


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv, t_process: float | None = None) -> int:
    args = parse(argv)
    spec = load_spec(args.workload)
    try:
        result = run(args, spec, t_process=t_process)
    except ChipMissing as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
