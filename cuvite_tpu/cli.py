"""Command-line driver: the equivalent of the `graphClustering` binary.

Collapses the reference's getopt flags + ~25 compile-time macros
(/root/reference/main.cpp:587-712, README:54-102) into one typed config.
Flag parity (reference -> here):

    -f FILE   -> --file FILE          (Vite binary input)
    -b        -> --balanced           (edge-balanced vertex partition)
    -c NC     -> --coloring NC        (distance-1 coloring, phase 0)
    -d NC     -> --vertex-ordering NC (color-based vertex ordering)
    -o        -> --output             (write .communities file)
    -t TYPE   -> --early-term TYPE    (1-4)
    -a ALPHA  -> --et-delta ALPHA     (probability decay, modes 2/4)
    -i        -> --threshold-cycling
    -g FILE   -> --ground-truth FILE  (LFR format comparison; 1-based ids
                 by default, pass --gt-zero-based for 0-based truth files —
                 the reference's -z flag flips the same offset,
                 main.cpp:627-629)
    -p        -> --one-phase
    -n NV     -> --generate NV        (in-memory RGG)
    -e PCT    -> --random-edges PCT
    -s FILE   -> --write-graph FILE   (save generated graph)
    -j        -> --just-process       (load/generate only, no clustering)
    USE_32_BIT_GRAPH -> --bits64 / default 32-bit
    nprocs    -> --shards N           (device mesh size)

Run: python -m cuvite_tpu.cli --file karate.bin --output
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="cuvite-tpu",
        description="TPU-native distributed Louvain community detection",
    )
    src = p.add_argument_group("input")
    src.add_argument("--file", "-f", help="Vite binary graph file")
    src.add_argument("--bits64", action="store_true",
                     help="64-bit vertex ids / double weights in the file")
    src.add_argument("--dist-ingest", action="store_true",
                     help="per-host sharded ingest: each process range-reads "
                          "only its shards' edges (the MPI-IO per-rank "
                          "slice analog, distgraph.cpp:69-203); requires "
                          "--file and the bucketed/pallas engines")
    src.add_argument("--generate", "-n", type=int, metavar="NV",
                     help="generate an in-memory RGG with NV vertices")
    src.add_argument("--rmat", type=int, metavar="SCALE",
                     help="generate an R-MAT graph with 2^SCALE vertices")
    src.add_argument("--edge-factor", type=int, default=16)
    src.add_argument("--random-edges", "-e", type=int, default=0, metavar="PCT",
                     help="percent extra random edges for generated graphs")
    src.add_argument("--seed", type=int, default=1)
    src.add_argument("--write-graph", "-s", metavar="FILE",
                     help="write the generated graph in Vite binary format")

    rt = p.add_argument_group("runtime")
    rt.add_argument("--platform", choices=["cpu", "tpu"], default=None,
                    help="pin the jax backend (like JAX_PLATFORMS)")

    dist = p.add_argument_group("distributed (multi-host)")
    dist.add_argument("--distributed", action="store_true",
                      help="connect this process to a multi-host run via "
                           "jax.distributed.initialize (MPI_Init analog, "
                           "main.cpp:67-70); every host runs the same "
                           "command")
    dist.add_argument("--coordinator", metavar="HOST:PORT",
                      help="coordinator address (default: "
                           "$CUVITE_COORDINATOR, else auto-detect on "
                           "Cloud TPU)")
    dist.add_argument("--num-processes", type=int,
                      help="total process count (default: "
                           "$CUVITE_NUM_PROCESSES or auto)")
    dist.add_argument("--process-id", type=int,
                      help="this process's rank (default: "
                           "$CUVITE_PROCESS_ID or auto)")

    run = p.add_argument_group("clustering")
    run.add_argument("--shards", type=int, default=1,
                     help="number of mesh devices (vertex shards)")
    run.add_argument("--mesh", metavar="DCNxICI",
                     help="2-D hybrid mesh 'dcn x ici' (e.g. 2x4) for the "
                          "two-level exchange: community tables replicate "
                          "only inside each fast ICI group, cross-group "
                          "traffic rides the sparse ghost protocol on the "
                          "slow DCN axis; 1xN is bit-compatible with "
                          "--shards N (auto = flat when dcn == 1)")
    run.add_argument("--balanced", "-b", action="store_true",
                     help="edge-balanced partition")
    run.add_argument("--threshold", type=float, default=1e-6)
    run.add_argument("--threshold-cycling", "-i", action="store_true")
    run.add_argument("--one-phase", "-p", action="store_true")
    run.add_argument("--early-term", "-t", type=int, choices=[1, 2, 3, 4],
                     help="early termination mode")
    run.add_argument("--et-delta", "-a", type=float, default=0.25)
    run.add_argument("--coloring", "-c", type=int, metavar="NC",
                     help="distance-1 coloring with NC max colors")
    run.add_argument("--vertex-ordering", "-d", type=int, metavar="NC",
                     help="color-based vertex ordering with NC max colors")
    run.add_argument("--engine", default="auto",
                     choices=["auto", "sort", "bucketed", "pallas", "fused"],
                     help="execution engine (auto = degree-bucketed)")
    run.add_argument("--exchange", default="auto",
                     choices=["auto", "sparse", "replicated", "twolevel"],
                     help="SPMD community exchange: 'sparse' = per-phase "
                          "ghost routing, O(owned+ghosts)/iteration (the "
                          "fillRemoteCommunities analog); 'replicated' = "
                          "all_gather of the full community vector; "
                          "'twolevel' = ICI-group tables + DCN ghost "
                          "routing (requires --mesh with dcn > 1); 'auto' "
                          "picks by graph size per phase")
    run.add_argument("--checkpoint-dir", metavar="DIR",
                     help="save inter-phase state after each phase "
                          "(the reference has no mid-run persistence)")
    run.add_argument("--resume", action="store_true",
                     help="resume from the latest checkpoint in "
                          "--checkpoint-dir")

    out = p.add_argument_group("output")
    out.add_argument("--output", "-o", action="store_true",
                     help="write <input>.communities")
    out.add_argument("--ground-truth", "-g", metavar="FILE",
                     help="compare against LFR ground truth")
    out.add_argument("--gt-zero-based", action="store_true",
                     help="ground-truth community ids start at 0")
    out.add_argument("--just-process", "-j", action="store_true")
    out.add_argument("--json", action="store_true",
                     help="emit a machine-readable summary line")
    out.add_argument("--trace", action="store_true",
                     help="print a stage-time breakdown, TEPS and RSS "
                          "high-water (the reference's per-stage "
                          "MPI_Wtime/getrusage instrumentation)")
    out.add_argument("--dist-stats", action="store_true",
                     help="print graph edge-distribution characteristics "
                          "(the reference's PRINT_DIST_STATS block, "
                          "distgraph.hpp:100-149)")
    out.add_argument("--diag-prefix", metavar="PREFIX",
                     help="write per-shard diagnostic files PREFIX.<shard> "
                          "(the reference's dat.out.<rank> streams, "
                          "main.cpp:101-110)")
    out.add_argument("--trace-out", metavar="FILE.jsonl",
                     help="write the flight recorder's structured "
                          "span/event trace as JSONL (see "
                          "OBSERVABILITY.md for the schema)")
    out.add_argument("--metrics-out", metavar="FILE.json",
                     help="write a machine-readable metrics summary: "
                          "per-phase convergence curves, stage times, "
                          "XLA compile events, HBM peaks")
    out.add_argument("--profile-dir", metavar="DIR",
                     help="capture a jax.profiler trace + device-memory "
                          "profile of the run under DIR (TensorBoard "
                          "format; allocator truth complementing the "
                          "flight recorder's logical HBM ledger)")
    out.add_argument("--quiet", action="store_true")
    return p


def validate(args) -> None:
    if not args.file and args.generate is None and args.rmat is None:
        raise SystemExit("Must specify --file, --generate or --rmat")
    if args.random_edges and args.generate is None:
        raise SystemExit("--random-edges requires --generate")
    if args.coloring and args.vertex_ordering:
        raise SystemExit("Cannot enable both --coloring and --vertex-ordering")
    if args.one_phase and args.threshold_cycling:
        raise SystemExit("Cannot combine --one-phase with --threshold-cycling")
    if args.early_term in (2, 4) and not (0.0 <= args.et_delta <= 1.0):
        raise SystemExit("--et-delta must be in [0, 1]")
    if args.resume and not args.checkpoint_dir:
        raise SystemExit("--resume requires --checkpoint-dir")
    if args.mesh:
        try:
            d, _, i = args.mesh.lower().replace("×", "x").partition("x")
            dcn, ici = int(d), int(i)
        except ValueError:
            raise SystemExit(f"--mesh must be DCNxICI (e.g. 2x4), "
                             f"got {args.mesh!r}")
        if dcn < 1 or ici < 1:
            raise SystemExit("--mesh factors must be >= 1")
        if args.shards not in (1, dcn * ici):
            raise SystemExit(f"--shards {args.shards} conflicts with "
                             f"--mesh {args.mesh} ({dcn * ici} devices)")
        if dcn > 1:
            if args.coloring or args.vertex_ordering:
                raise SystemExit("--mesh with dcn > 1 (two-level exchange) "
                                 "is incompatible with --coloring/"
                                 "--vertex-ordering")
            if args.engine in ("sort", "fused"):
                raise SystemExit("--mesh with dcn > 1 requires the "
                                 "bucketed/pallas engines")
            if args.dist_ingest:
                raise SystemExit("--mesh with dcn > 1 does not support "
                                 "--dist-ingest yet")
            if args.exchange == "replicated":
                raise SystemExit("--mesh with dcn > 1 runs the two-level "
                                 "exchange; --exchange replicated needs a "
                                 "flat mesh")
    elif args.exchange == "twolevel":
        raise SystemExit("--exchange twolevel requires --mesh DCNxICI "
                         "with dcn > 1")
    if args.dist_ingest:
        if not args.file:
            raise SystemExit("--dist-ingest requires --file")
        if args.engine not in ("auto", "bucketed", "pallas"):
            raise SystemExit("--dist-ingest supports only the "
                             "bucketed/pallas engines")
        if (args.coloring or args.vertex_ordering or args.checkpoint_dir
                or args.write_graph):
            raise SystemExit("--dist-ingest is incompatible with "
                             "--coloring/--vertex-ordering/--checkpoint-dir/"
                             "--write-graph (they need the full graph on "
                             "every host)")
    if args.checkpoint_dir and args.one_phase:
        raise SystemExit("--checkpoint-dir is incompatible with --one-phase")


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    validate(args)

    if args.platform:
        # Before any jax backend touch.
        import jax

        jax.config.update("jax_platforms", args.platform)

    if args.distributed:
        # Before any jax backend touch: after this, jax.devices() is the
        # GLOBAL device list across all hosts and --shards may span it.
        from cuvite_tpu.comm.multihost import initialize

        initialize(coordinator=args.coordinator,
                   num_processes=args.num_processes,
                   process_id=args.process_id)
        import jax

        if jax.process_index() != 0:
            # Output and chatter are rank-0's job (the reference gates its
            # output/report paths on me == 0, main.cpp:363-406, :521-559);
            # every process still computes the identical result.  File
            # writers must also be gated or hosts sharing a filesystem
            # would write the same paths concurrently.
            args.quiet = True
            args.output = False
            args.json = False
            args.ground_truth = None
            args.trace = False
            args.dist_stats = False
            args.diag_prefix = None
            args.write_graph = None
            args.trace_out = None
            args.metrics_out = None
            args.profile_dir = None

    from cuvite_tpu.core.graph import Graph  # noqa: F401 (re-export context)
    from cuvite_tpu.evaluate.compare import (
        compare_communities, load_ground_truth, write_communities,
    )
    from cuvite_tpu.evaluate.modularity import modularity_gated
    from cuvite_tpu.io.generate import generate_rgg, generate_rmat
    from cuvite_tpu.io.vite import read_vite, write_vite
    from cuvite_tpu.louvain.driver import louvain_phases

    t0 = time.perf_counter()
    if args.file and args.dist_ingest:
        from cuvite_tpu.io.dist_ingest import DistVite

        graph = DistVite.load(args.file, args.shards, bits64=args.bits64,
                              balanced=args.balanced)
        name = args.file
    elif args.file:
        graph = read_vite(args.file, bits64=args.bits64)
        name = args.file
    elif args.rmat is not None:
        graph = generate_rmat(args.rmat, edge_factor=args.edge_factor,
                              seed=args.seed)
        name = f"rmat{args.rmat}"
    else:
        graph = generate_rgg(args.generate, nshards=args.shards,
                             random_edge_percent=args.random_edges,
                             seed=args.seed)
        name = f"rgg{args.generate}"
    load_s = time.perf_counter() - t0
    if not args.quiet:
        print(f"Loaded graph: {graph.num_vertices} vertices, "
              f"{graph.num_edges} directed edges ({load_s:.2f}s)")

    if args.write_graph:
        write_vite(args.write_graph, graph, bits64=args.bits64)
        if not args.quiet:
            print(f"Wrote graph to {args.write_graph}")
    if args.just_process:
        return 0

    from cuvite_tpu.utils.trace import Tracer

    # Flight recorder (ISSUE 6): any of --trace-out / --metrics-out /
    # --profile-dir attaches one; the drivers thread their telemetry
    # through the tracer unconditionally, so a run without these flags
    # pays nothing.
    import contextlib

    recorder = None
    rec_ctx = contextlib.nullcontext()
    if args.trace_out or args.metrics_out or args.profile_dir:
        from cuvite_tpu.obs import NO_TRACE, FlightRecorder, JsonlTraceSink

        # Without --trace-out the recorder serves --metrics-out /
        # --profile-dir only (compile events + HBM ledger): NO_TRACE
        # skips the emitter so no unread span records accumulate.
        sink = JsonlTraceSink(args.trace_out) if args.trace_out else NO_TRACE
        recorder = FlightRecorder(sink, profile_dir=args.profile_dir)
        rec_ctx = recorder

    tracer = Tracer(enabled=args.trace, recorder=recorder)
    with rec_ctx:
        res = louvain_phases(
            graph,
            nshards=args.shards,
            mesh_shape=args.mesh,
            threshold=args.threshold,
            threshold_cycling=args.threshold_cycling,
            one_phase=args.one_phase,
            balanced=args.balanced,
            et_mode=args.early_term or 0,
            et_delta=args.et_delta,
            engine=args.engine,
            exchange=args.exchange,
            coloring=args.coloring or 0,
            vertex_ordering=args.vertex_ordering or 0,
            verbose=not args.quiet,
            tracer=tracer,
            checkpoint_dir=args.checkpoint_dir,
            resume=args.resume,
            dist_stats=args.dist_stats,
            diag_prefix=args.diag_prefix,
        )
    if args.trace:
        print(tracer.report())
    if args.trace_out and not args.quiet:
        print(f"Wrote trace to {args.trace_out}")

    if args.dist_ingest:
        # No process holds the full graph; the driver's distributed f64
        # recompute already produced the reported value.
        q = res.modularity
    else:
        # Size-gated: the dense host oracle only below the O(E)-gather
        # ceiling (VERDICT r5 weak #7); huge graphs report the driver's
        # ds-exact device value instead.
        q, used_oracle = modularity_gated(graph, res.communities,
                                          res.modularity)
        if not used_oracle and not args.quiet:
            print(f"# host modularity oracle skipped: {graph.num_edges} "
                  "edges exceed the O(E) host-gather ceiling "
                  "(CUVITE_HOST_ORACLE_MAX_EDGES); reporting the "
                  "driver's ds-exact device value")
    teps = sum(p.num_edges * p.iterations for p in res.phases) / max(
        sum(p.seconds for p in res.phases), 1e-9)
    if not args.quiet:
        print(f"Final modularity: {q:.6f} "
              f"({res.num_communities} communities, "
              f"{res.total_iterations} iterations, "
              f"{res.total_seconds:.2f}s, TEPS {teps:.3g})")

    if args.output:
        out = name + ".communities"
        write_communities(out, res.communities)
        if not args.quiet:
            print(f"Wrote communities to {out}")

    if args.ground_truth:
        truth = load_ground_truth(args.ground_truth,
                                  zero_based=args.gt_zero_based)
        cmp_res = compare_communities(truth, res.communities)
        print(cmp_res.report())

    summary = {
        "graph": name,
        "nv": graph.num_vertices,
        "ne": graph.num_edges,
        "modularity": q,
        "communities": res.num_communities,
        "iterations": res.total_iterations,
        "phases": len(res.phases),
        "seconds": res.total_seconds,
        "teps": teps,
    }
    if getattr(res, "exchange_stats", None):
        # The SPMD run's exchange arm (ISSUE 18): mode plus — on a
        # two-level run — dcn/ici and the per-device table/ghost bytes;
        # perf_regress keeps flat and two-level records in separate arms
        # on this block.
        xs = res.exchange_stats
        summary["exchange"] = {
            k: xs[k] for k in ("mode", "dcn", "ici",
                               "table_bytes_per_device", "ghost_bytes")
            if k in xs}
    if args.json:
        print(json.dumps(summary))

    if args.metrics_out:
        from cuvite_tpu.utils.trace import rss_high_water_mb

        metrics = dict(summary)
        metrics["stages"] = tracer.breakdown()
        metrics["rss_mb"] = round(rss_high_water_mb(), 1)
        if res.convergence:
            metrics["convergence"] = [pc.to_dict()
                                      for pc in res.convergence]
        if recorder is not None:
            metrics["compile_events"] = recorder.compile_events
            metrics["hbm_peak_by_buffer"] = recorder.ledger.peak_by_buffer
            metrics["hbm_snapshots"] = recorder.ledger.snapshots
        with open(args.metrics_out, "w", encoding="utf-8") as f:
            json.dump(metrics, f, indent=1)
            f.write("\n")
        if not args.quiet:
            print(f"Wrote metrics to {args.metrics_out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
