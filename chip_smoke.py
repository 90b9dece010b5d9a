#!/usr/bin/env python3
"""Chip smoke of the per-graph Louvain driver, in one process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded path on four chips

One chip: karate (Q ~ 0.4087, 4 communities), the small golden graph
``powerlaw-test/default`` through ``engine='pallas'`` (every degree
class up to PALLAS_MAX_WIDTH through the compiled row-argmax kernel),
then the real-size golden graph ``powerlaw-1e8/default`` (6.25 M
vertices, 99.86 M directed edges, synthesized from seed 1 and checked
against the sha256 its envelope was taken on) through the default
engine, checked against the f64 host modularity oracle and the golden
envelope.  One run, compiles included: a second, warm run would cost
another ~180 s of the 1200 s the smoke may take.

``--chips 4``: the golden graph (or ``--edges N`` of the same
synthesizer) sharded over four chips (``nshards=4, exchange='sparse'``)
and on one chip; the communities must be bit-identical and the plan
arrays must span four devices.

Every check prints a line.  The last line of stdout is
``{"ok": true, "device": {...}}`` only when the platform is a TPU and
every check passed; otherwise the script exits non-zero without it.
The script never starts a child process: the chip belongs to this one.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from typing import NamedTuple

ROOT = os.path.dirname(os.path.abspath(__file__))
DATA_DIR = os.path.join(ROOT, "workloads_data")
KARATE_Q = 0.4087
ORACLE_TOL = 1e-4


class CheckFailed(Exception):
    pass


class Golden(NamedTuple):
    """A golden-envelope dataset the synthesizer rebuilds offline."""

    dataset: str
    edges: int
    seed: int


GOLDEN_1E8 = Golden("powerlaw-1e8", 10**8, 1)
GOLDEN_TEST = Golden("powerlaw-test", 40000, 7)


def say(*parts) -> None:
    print(*parts, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    say(f"check ok: {what}")


def device_info(chips: int) -> dict:
    """Versions and the device JAX reports; fails off the TPU."""
    from importlib import metadata

    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(f"versions: jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu}")
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say(f"device: platform={dev['platform']} device_kind={dev['kind']} "
        f"count={dev['count']}")
    check(dev["platform"] == "tpu", f"platform is tpu (got "
          f"{dev['platform']!r})")
    # The kernels run in interpret mode off the TPU backend only.
    check(jax.default_backend() == "tpu",
          "default backend is tpu: Pallas kernels compiled, none in "
          "interpret mode")
    check(dev["count"] >= chips, f"{chips} device(s) visible")
    return dev


def check_native() -> None:
    from cuvite_tpu import native

    ok = native.available()
    say(f"native library: {'loaded ' + native._so_path() if ok else 'NOT loaded'}")
    check(ok, "native host library loaded")


def karate_graph():
    import networkx as nx
    import numpy as np

    from cuvite_tpu import Graph

    e = np.array(nx.karate_club_graph().edges(), dtype=np.int64)
    return Graph.from_edges(34, e[:, 0], e[:, 1])


def check_oracle(graph, res, label: str) -> float:
    from cuvite_tpu.evaluate.modularity import modularity

    q = modularity(graph, res.communities)
    say(f"{label} oracle: driver Q={res.modularity:.6f} host f64 Q={q:.6f} "
        f"|diff|={abs(q - res.modularity):.2e}")
    check(abs(q - res.modularity) < ORACLE_TOL,
          f"{label} Q within {ORACLE_TOL} of the f64 oracle")
    return q


def report_phases(res, label: str) -> None:
    for p in res.phases:
        cov = ("none (XLA paths only)" if p.pallas_coverage is None
               else f"{p.pallas_coverage:.4f}")
        say(f"{label} phase {p.phase}: nv={p.num_vertices} "
            f"ne={p.num_edges} iterations={p.iterations} "
            f"seconds={p.seconds:.3f} pallas_coverage={cov}")


def synth_golden(golden: Golden, data_dir: str, truth: bool):
    """Write the golden graph to ``data_dir`` and check its sha256
    against the provenance its envelope was taken on."""
    from cuvite_tpu.io.vite import read_vite
    from cuvite_tpu.workloads.golden import golden_key, load_golden
    from cuvite_tpu.workloads.synth import synthesize

    entry = load_golden()["entries"].get(golden_key(golden.dataset), {})
    os.makedirs(data_dir, exist_ok=True)
    path = os.path.join(data_dir, f"powerlaw_{golden.edges}.vite")
    t0 = time.perf_counter()
    prov = synthesize(path, golden.edges, seed=golden.seed,
                      write_truth=truth)
    say(f"{golden.dataset}: synthesized in "
        f"{time.perf_counter() - t0:.1f}s sha256={prov['sha256']}")
    pinned = re.search(r"sha256 ([0-9a-f]{64})", entry.get("provenance", ""))
    if pinned:
        check(prov["sha256"] == pinned.group(1),
              f"{golden.dataset} sha256 matches the golden provenance")
    t0 = time.perf_counter()
    graph = read_vite(path, bits64=False)
    say(f"{golden.dataset}: read in {time.perf_counter() - t0:.1f}s "
        f"nv={graph.num_vertices} ne={graph.num_edges}")
    return graph, prov


def check_golden(golden: Golden, res, prov) -> None:
    from cuvite_tpu.workloads.golden import measure_run, verify

    measured = measure_run(res.communities, res,
                           truth_path=prov.get("truth_path"),
                           provenance=prov.get("source"))
    ok, problems = verify(golden.dataset, "default", measured)
    say(f"{golden.dataset} golden: Q={measured['modularity']:.6f} "
        f"communities={measured['communities']} "
        f"phases={measured['phases']} "
        f"f_score={measured.get('f_score', float('nan')):.6f} "
        f"verdict={'ok' if ok else problems}")
    check(ok, f"{golden.dataset}/default inside its golden envelope")


def smoke_one_chip(golden: Golden = GOLDEN_1E8, data_dir: str = DATA_DIR,
                   device=None) -> None:
    from cuvite_tpu.louvain.driver import louvain_phases
    from cuvite_tpu.obs import NO_TRACE, FlightRecorder

    # 1. karate: the quick phase.
    g = karate_graph()
    res = louvain_phases(g)
    report_phases(res, "karate")
    say(f"karate: Q={res.modularity:.6f} "
        f"communities={res.num_communities}")
    check(abs(res.modularity - KARATE_Q) < 1e-3 and
          res.num_communities == 4, "karate Q ~ 0.4087 with 4 communities")
    check_oracle(g, res, "karate")

    # 2. the row-argmax kernel, every class through engine='pallas'.
    g, prov = synth_golden(GOLDEN_TEST, data_dir, truth=True)
    res = louvain_phases(g, engine="pallas")
    report_phases(res, "pallas")
    hits = sorted(w for w in (res.pallas_width_hits or {}) if w)
    say(f"pallas: coverage={res.pallas_coverage} kernel widths={hits}")
    check(bool(res.pallas_coverage) and max(hits, default=0) > 32,
          "row-argmax kernel ran, wide (fori_loop) classes included")
    check_oracle(g, res, "pallas")
    check_golden(GOLDEN_TEST, res, prov)

    # 3. the real-size golden graph through the default engine.
    g, prov = synth_golden(golden, data_dir, truth=True)
    with FlightRecorder(NO_TRACE) as rec:
        t0 = time.perf_counter()
        res = louvain_phases(g)
        wall = time.perf_counter() - t0
    compile_s = sum(e["dur_s"] for e in rec.compile_events)
    report_phases(res, golden.dataset)
    say(f"{golden.dataset}: clustering wall {wall:.1f}s, of which "
        f"compiles {compile_s:.1f}s ({len(rec.compile_events)} programs); "
        f"phases={len(res.phases)} iterations={res.total_iterations}")
    if device is not None:
        stats = device.memory_stats() or {}
        say(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    check_oracle(g, res, golden.dataset)
    check_golden(golden, res, prov)


def smoke_sharded(golden: Golden = GOLDEN_1E8, data_dir: str = DATA_DIR,
                  nshards: int = 4) -> None:
    """The sharded path and the one-chip run it must match."""
    import numpy as np

    from cuvite_tpu.louvain.driver import louvain_phases
    from cuvite_tpu.obs import NO_TRACE, FlightRecorder
    from cuvite_tpu.obs.memory import DeviceMemoryLedger
    from cuvite_tpu.utils.trace import Tracer

    class SpanLedger(DeviceMemoryLedger):
        """The HBM ledger, also noting the devices each category's
        arrays live on."""

        def __init__(self):
            super().__init__()
            self.devices = {}

        def track(self, category, *arrays):
            super().track(category, *arrays)
            for a in arrays:
                sh = getattr(a, "sharding", None)
                if sh is not None:
                    self.devices.setdefault(category, set()).update(
                        sh.device_set)

    g, _ = synth_golden(golden, data_dir, truth=False)
    rec = FlightRecorder(NO_TRACE, watch_compiles=False)
    rec.ledger = SpanLedger()
    t0 = time.perf_counter()
    sharded = louvain_phases(g, nshards=nshards, exchange="sparse",
                             tracer=Tracer(recorder=rec))
    say(f"nshards={nshards} sparse: {time.perf_counter() - t0:.1f}s "
        f"Q={sharded.modularity:.6f} "
        f"communities={sharded.num_communities}")
    report_phases(sharded, f"nshards={nshards}")
    spans = {k: len(v) for k, v in rec.ledger.devices.items()}
    say(f"devices per tracked category: {spans}")
    check(spans.get("plans", 0) == nshards,
          f"plan arrays span {nshards} distinct devices")
    t0 = time.perf_counter()
    single = louvain_phases(g, nshards=1)
    say(f"nshards=1: {time.perf_counter() - t0:.1f}s "
        f"Q={single.modularity:.6f} communities={single.num_communities}")
    check(np.array_equal(sharded.communities, single.communities),
          f"nshards={nshards} communities bit-identical to nshards=1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--edges", type=float, default=None,
                    help="--chips 4 only: synthesize this many directed "
                         "edges instead of the golden graph's 1e8")
    args = ap.parse_args(argv)
    if args.edges is not None and args.chips == 1:
        ap.error("--edges applies to --chips 4 only")
    sys.path.insert(0, ROOT)
    try:
        from cuvite_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        dev = device_info(args.chips)
        check_native()
        if args.chips == 1:
            import jax

            smoke_one_chip(device=jax.devices()[0])
        else:
            golden = GOLDEN_1E8 if args.edges is None else Golden(
                f"powerlaw-{args.edges:g}", int(args.edges), 1)
            smoke_sharded(golden, nshards=args.chips)
    except CheckFailed as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    say(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
