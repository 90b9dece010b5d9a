"""Tracing / profiling / diagnostics.

The reference instruments every stage with MPI_Wtime pairs printed per
rank (/root/reference/main.cpp:241-258, :353-358, :411-426, the
DEBUG_PRINTF stage breakdowns in louvain.cpp:472-538, and the
PRINT_TIMEDS GPU timers, louvain_cuda.cu:2456-2461), tracks the memory
high-water with getrusage (main.cpp:142-150), and routes diagnostics to
per-rank `dat.out.<rank>` files (main.cpp:101-110).

Here that collapses into one Tracer object: named accumulating stage
timers (wall clock; device work is timed around blocking host syncs, the
only boundaries that exist under jit), RSS high-water, TEPS accounting
(main.cpp:448, :509), and optional per-shard diag files.

An enabled Tracer also writes every span it opens into the JAX
profiler's trace (``jax.profiler.TraceAnnotation``): ``stage.<name>``
for a stage, the bare name for an envelope span (``phase``, ``pack``,
...).  Both sinks, the profiler's and the flight recorder's, are fed
from one call, so they bracket the same code, and a profile of the run
puts the host stages on the device trace's clock.
"""

from __future__ import annotations

import contextlib
import math
import os
import resource
import time

# Profiler annotation of a stage; the benchmark's trace reduction reads
# spans with this prefix as stages.
STAGE_PREFIX = "stage."


def _annotation(name: str):
    """An unopened ``jax.profiler.TraceAnnotation``.  jax is imported
    here, not at the top, so this module imports without it."""
    import jax.profiler

    return jax.profiler.TraceAnnotation(name)


def rss_high_water_mb() -> float:
    """Peak resident set size of this process in MiB (the reference prints
    getrusage ru_maxrss the same way, main.cpp:142-150)."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    # ru_maxrss is KiB on Linux.
    return ru.ru_maxrss / 1024.0


class Tracer:
    """Accumulating named stage timers + counters, and (since ISSUE 6) a
    thin facade over the obs flight recorder: attach a
    ``cuvite_tpu.obs.FlightRecorder`` and every ``stage()`` window also
    becomes a nested span in the structured trace, ``event()`` /
    ``begin_span()`` / ``track()`` forward to the emitter/HBM ledger, and the
    drivers' telemetry (convergence rows, exchange-plan stats, memory
    snapshots) lands in the record stream.  Without a recorder those
    calls are no-ops — the drivers thread them unconditionally at zero
    cost — except that an enabled tracer's spans still reach the
    profiler.

    Usage::

        tr = Tracer()
        with tr.stage("load"):
            ...
        tr.count("iterations", n)
        print(tr.report())
    """

    def __init__(self, enabled: bool = True, recorder=None):
        # A recorder implies recording: --trace-out without --trace must
        # still time the stages its spans report.
        self.enabled = enabled or recorder is not None
        self.recorder = recorder
        self.emitter = recorder.emitter if recorder is not None else None
        self.times: dict[str, float] = {}
        self.calls: dict[str, int] = {}
        self.counters: dict[str, float] = {}

    @contextlib.contextmanager
    def stage(self, name: str):
        if not self.enabled:
            yield
            return
        em = self.emitter
        with _annotation(STAGE_PREFIX + name):
            sid = em.begin(name) if em is not None else None
            t0 = time.perf_counter()
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                if em is not None:
                    em.end(sid, dur_s=dt)
                self.times[name] = self.times.get(name, 0.0) + dt
                self.calls[name] = self.calls.get(name, 0) + 1

    def count(self, name: str, value: float = 1) -> None:
        if self.enabled:
            self.counters[name] = self.counters.get(name, 0) + value

    # -- flight-recorder facade (no-ops without an attached recorder) -------

    def event(self, name: str, **attrs) -> None:
        """Point event in the structured trace."""
        if self.emitter is not None:
            self.emitter.event(name, **attrs)

    def begin_span(self, name: str, **attrs):
        """Open a span whose extent cannot be a ``with`` block (the
        driver's per-phase envelope spans a loop body with breaks).
        Returns an opaque handle for :meth:`end_span`: the emitter's span
        id and the open profiler annotation (None when disabled)."""
        if not self.enabled:
            return None
        ann = _annotation(name)
        ann.__enter__()
        sid = self.emitter.begin(name, **attrs) \
            if self.emitter is not None else None
        return sid, ann

    def end_span(self, handle, **attrs) -> None:
        if handle is None:
            return
        sid, ann = handle
        try:
            if self.emitter is not None and sid is not None:
                self.emitter.end(sid, **attrs)
        finally:
            ann.__exit__(None, None, None)

    def set_phase(self, phase) -> None:
        """Tag subsequent records with the running phase index."""
        if self.emitter is not None:
            self.emitter.phase = phase

    def track(self, category: str, *arrays) -> None:
        """Account device buffers to the HBM ledger by category."""
        if self.recorder is not None:
            self.recorder.ledger.track(category, *arrays)

    def ledger_phase_begin(self) -> None:
        if self.recorder is not None:
            self.recorder.ledger.begin_phase()

    def ledger_snapshot(self, phase=None) -> None:
        """Snapshot the ledger at a phase boundary and emit it."""
        if self.recorder is not None:
            snap = self.recorder.ledger.snapshot(phase)
            self.event("hbm", **snap)

    # Stage names the drivers use, in pipeline order.  These are the
    # bench record's REQUIRED per-stage fields (ISSUE 3 satellite;
    # coalesce since ISSUE 8, rebin since ISSUE 19): coarsen_s —
    # inter-phase graph rebuild (host or device); coalesce_s — the
    # device relabel+coalesce slice, NESTED inside coarsen_s (coarsen_s
    # CONTAINS coalesce_s; 0.0 on the host-compaction path), split out
    # so the round-7 sort tax is a measured field; rebin_s — the device
    # plan re-bin of a coarse phase (coarsen/rebin.py; runs NESTED
    # inside the driver's plan stage, so plan_s CONTAINS rebin_s; 0.0
    # on the host BucketPlan.build path and on non-bucketed engines);
    # upload_s — host->device placement of slabs/plans; iterate_s — the
    # jitted phase loops.  Note upload runs NESTED inside the driver's
    # plan stage on the per-phase engine path, so there plan_s CONTAINS
    # upload_s (the fused driver's stages are disjoint).  Two more
    # stages nest in the per-phase plan and are not canonical (absent
    # on the fused driver): partition — the host DistGraph.build — and
    # bucket — the host BucketPlan.build / build_stacked_plans degree
    # bucketing and its assembly permutation.  So on that path plan_s
    # CONTAINS partition_s, bucket_s, upload_s and rebin_s, all four
    # disjoint.  evaluate (the per-phase modularity recompute) stands
    # beside iterate and coarsen.
    CANONICAL_STAGES = ("coarsen", "coalesce", "rebin", "upload",
                        "iterate")

    def breakdown(self) -> dict:
        """Per-stage seconds for machine consumers (the bench JSON's
        ``stages`` field): always carries ``<stage>_s`` for every
        CANONICAL_STAGES entry (0.0 when the stage never ran), plus any
        other recorded stage under the same naming.

        FULL precision: rounding here (the historical ``round(v, 3)``)
        erased sub-millisecond stages outright — upload on a tiny graph
        reported 0.0, making real-vs-absent indistinguishable to the
        regression gate.  Human-facing rounding lives in ``report()``."""
        out = {k + "_s": self.times.get(k, 0.0)
               for k in self.CANONICAL_STAGES}
        for k, v in sorted(self.times.items()):
            out.setdefault(k + "_s", v)
        return out

    def teps(self) -> float:
        """Traversed edges per second: counter 'traversed_edges' over the
        'iterate' stage WALL time.  Unlike the steady-state bench metric
        (bench.py warm-up excludes compilation, cf. main.cpp:499-518),
        this includes any one-time XLA compile that ran inside the stage —
        the report labels it accordingly."""
        t = self.times.get("iterate", 0.0)
        return self.counters.get("traversed_edges", 0.0) / t if t else 0.0

    def report(self) -> str:
        lines = ["stage breakdown (s):"]
        total = sum(self.times.values())
        for name, t in sorted(self.times.items(), key=lambda kv: -kv[1]):
            lines.append(
                f"  {name:<16} {t:9.3f}  ({self.calls[name]}x, "
                f"{100.0 * t / total if total else 0.0:4.1f}%)"
            )
        for name, v in sorted(self.counters.items()):
            lines.append(f"  {name:<16} {v:g}")
        if self.counters.get("traversed_edges"):
            lines.append(
                f"  TEPS (wall, incl. compile) {self.teps():.4g}"
            )
        lines.append(f"  rss high-water   {rss_high_water_mb():.0f} MiB")
        return "\n".join(lines)


def dist_stats_report(dg, ghost_counts=None) -> str:
    """Edge-distribution characteristics of a DistGraph partition: the
    analog of the reference's PRINT_DIST_STATS block
    (/root/reference/distgraph.hpp:100-149), which Allreduces per-rank
    local edge counts and prints min/max/mean/variance/stddev on rank 0.
    Here the partition is host-resident, so the moments are computed
    directly; ghost counts (from the phase ExchangePlan) are appended when
    available — the piece the reference's stats lack."""
    counts = [sh.n_real_edges for sh in dg.shards]
    n = max(len(counts), 1)
    mean = sum(counts) / n
    avg_sq = sum(c * c for c in counts) / n
    var = abs(avg_sq - mean * mean)
    lines = [
        "-" * 55,
        "Graph edge distribution characteristics",
        "-" * 55,
        f"Number of vertices: {dg.total_vertices}",
        f"Number of edges: {dg.total_edges}",
        f"Number of shards: {dg.nshards}",
        f"Maximum number of edges: {max(counts)}",
        f"Minimum number of edges: {min(counts)}",
        f"Mean number of edges: {mean:g}",
        f"Variance: {var:g}",
        f"Standard deviation: {math.sqrt(var):g}",
    ]
    if ghost_counts is not None:
        lines.append(
            f"Ghost vertices per shard: max {max(ghost_counts)}, "
            f"min {min(ghost_counts)}, "
            f"mean {sum(ghost_counts) / max(len(ghost_counts), 1):g}")
    lines.append("-" * 55)
    return "\n".join(lines)


class ShardDiag:
    """Per-shard diagnostic text files: the analog of the reference's
    per-rank `dat.out.<rank>` streams (/root/reference/main.cpp:101-110).
    One `<prefix>.<shard>` file per shard, appended a line per call; files
    open lazily on first write."""

    def __init__(self, prefix: str, nshards: int):
        self.prefix = prefix
        self.nshards = nshards
        self._files: dict[int, object] = {}

    def write(self, shard: int, line: str) -> None:
        f = self._files.get(shard)
        if f is None:
            d = os.path.dirname(self.prefix)
            if d:
                os.makedirs(d, exist_ok=True)
            # Truncate on first open (like the reference's per-rank
            # ofstreams) so reruns don't mix stale lines into the files.
            f = open(f"{self.prefix}.{shard}", "w")
            self._files[shard] = f
        f.write(line.rstrip("\n") + "\n")

    def close(self) -> None:
        for f in self._files.values():
            f.close()
        self._files.clear()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class NullTracer(Tracer):
    def __init__(self):
        super().__init__(enabled=False)
