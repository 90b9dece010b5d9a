"""Workloads CLI.

    python -m cuvite_tpu.workloads fetch com-orkut --dest workloads_data
    python -m cuvite_tpu.workloads synth --edges 1e8 --profile powerlaw
    python -m cuvite_tpu.workloads synth --edges 1e7 --profile lfr --mu 0.4
    python -m cuvite_tpu.workloads convert in.txt.gz --out out.vite
    python -m cuvite_tpu.workloads bench --file out.vite
    python -m cuvite_tpu.workloads verify-golden --dataset powerlaw-1e8 \
        --file out.vite [--update-golden]

Every artifact lands next to a ``.provenance.json`` describing where it
came from (fetched + checksum, or offline-synthesized + parameters), so
a BASELINE row can always say which it was.
"""

from __future__ import annotations

import argparse
import json
import sys

DEFAULT_DATA_DIR = "workloads_data"


def _cmd_fetch(args) -> int:
    from cuvite_tpu.workloads.registry import DATASETS, fetch

    if args.list:
        for name, ds in sorted(DATASETS.items()):
            print(f"{name}: |V|={ds.num_vertices} "
                  f"|E|={ds.num_edges_undirected} (undirected) "
                  f"fmt={ds.fmt} sha256={'pinned' if ds.sha256 else 'TOFU'}")
        return 0
    payload = fetch(args.name, args.dest,
                    offline_fallback=not args.no_offline_fallback,
                    synth_edges=args.synth_edges,
                    keep_download=args.keep_download)
    print(json.dumps({"source": payload["source"],
                      "result": payload.get("result")}))
    return 0


def _lfr_args(args) -> dict:
    return {"gamma": args.gamma, "beta": args.beta,
            "mean_degree": args.mean_degree, "max_degree": args.max_degree,
            "cmin": args.cmin, "cmax": args.cmax}


def _cmd_synth(args) -> int:
    import os

    from cuvite_tpu.workloads.synth import synthesize, synthesize_many

    out = args.out
    if out is None:
        os.makedirs(DEFAULT_DATA_DIR, exist_ok=True)
        out = os.path.join(DEFAULT_DATA_DIR,
                           f"{args.profile}_{int(args.edges)}.vite")
    if args.many:
        # K small graphs on distinct splitmix64 streams, one provenance
        # file for the set (serving benches/tests, ISSUE 9).
        prefix = out[:-5] if out.endswith(".vite") else out
        payload = synthesize_many(
            prefix, args.many, edges=int(args.edges),
            profile=args.profile, seed=args.seed, alpha=args.alpha,
            mu=args.mu, overlap=args.overlap,
            edge_factor=args.edge_factor, bits64=args.bits64,
            write_truth=not args.no_truth, **_lfr_args(args),
        )
        print(json.dumps({
            "out_prefix": prefix, "count": payload["count"],
            "provenance": prefix + ".many.provenance.json",
            "graphs": [m["path"] for m in payload["graphs"]]}))
        return 0
    payload = synthesize(
        out, edges=int(args.edges), profile=args.profile, seed=args.seed,
        alpha=args.alpha, mu=args.mu, overlap=args.overlap,
        edge_factor=args.edge_factor, bits64=args.bits64,
        write_truth=not args.no_truth, **_lfr_args(args),
    )
    line = {"out": out, "result": payload["result"],
            "sha256": payload["sha256"]}
    if "lfr" in payload:
        line["lfr"] = payload["lfr"]
    if args.churn:
        # Deterministic insert/delete stream against the graph just
        # written (read back, so the churn indexes the REALIZED edge
        # set), for the streaming warm-start A/B (ISSUE 17).
        from cuvite_tpu.io.vite import read_vite
        from cuvite_tpu.workloads.synth import write_churn

        graph = read_vite(out, bits64=args.bits64)
        churn = write_churn(out, graph, frac=args.churn,
                            seed=args.churn_seed, batches=args.churn_batches)
        line["churn"] = {"npz": out + ".churn.npz",
                         "sha256": churn["sha256"],
                         "frac": churn["churn_frac"],
                         "batches": churn["batches"]}
    print(json.dumps(line))
    return 0


def _cmd_convert(args) -> int:
    from cuvite_tpu.workloads.convert import convert
    from cuvite_tpu.workloads.synth import write_provenance

    stats = convert(args.input, args.out, fmt=args.format,
                    bits64=args.bits64, symmetrize=args.symmetrize,
                    relabel=args.relabel)
    write_provenance(args.out, {"source": "converted",
                                "input": args.input,
                                "result": stats.to_dict()})
    print(json.dumps(stats.to_dict()))
    return 0


def _cmd_bench(args, extra) -> int:
    from cuvite_tpu.workloads.bench import main as bench_main

    return bench_main(extra)


def _cmd_verify_golden(args) -> int:
    import numpy as np  # noqa: F401  (louvain result arrays)

    from cuvite_tpu.io.vite import read_vite
    from cuvite_tpu.louvain.driver import louvain_phases
    from cuvite_tpu.workloads.golden import measure_run, verify
    from cuvite_tpu.workloads.registry import load_provenance

    graph = read_vite(args.file, bits64=args.bits64)
    res = louvain_phases(graph, engine=args.engine, verbose=False)
    prov = load_provenance(args.file)
    truth = args.truth
    if truth is None and prov and prov.get("truth_path"):
        truth = prov["truth_path"]
    measured = measure_run(res.communities, res, truth_path=truth,
                           zero_based_truth=args.truth_zero_based,
                           provenance=prov.get("source") if prov else None)
    ok, problems = verify(args.dataset, args.config, measured,
                          path=args.golden, update=args.update_golden)
    print(json.dumps({"dataset": args.dataset, "config": args.config,
                      "measured": measured, "ok": ok,
                      "problems": problems,
                      "updated": bool(args.update_golden)}))
    return 0 if ok else 1


def build_parser() -> argparse.ArgumentParser:
    from cuvite_tpu.workloads.convert import FORMATS
    from cuvite_tpu.workloads.golden import DEFAULT_GOLDEN_PATH
    from cuvite_tpu.workloads.synth import PROFILES

    p = argparse.ArgumentParser(prog="python -m cuvite_tpu.workloads",
                                description=__doc__.split("\n")[0])
    sub = p.add_subparsers(dest="cmd", required=True)

    f = sub.add_parser("fetch", help="download+verify+convert a dataset "
                                     "(offline: synthesize a stand-in)")
    f.add_argument("name", nargs="?", default="")
    f.add_argument("--dest", default=DEFAULT_DATA_DIR)
    f.add_argument("--list", action="store_true")
    f.add_argument("--no-offline-fallback", action="store_true")
    f.add_argument("--synth-edges", type=float, default=None,
                   help="edge count of the offline stand-in")
    f.add_argument("--keep-download", action="store_true")

    s = sub.add_parser("synth", help="synthesize a graph with planted "
                                     "communities as a Vite file")
    s.add_argument("--edges", type=float, required=True,
                   help="target directed edge records (e.g. 1e8); lfr "
                        "takes edges / mean-degree vertices")
    s.add_argument("--profile", default="powerlaw", choices=PROFILES,
                   help="lfr: the LFR benchmark construction; powerlaw: "
                        "the older stand-in with overlap")
    s.add_argument("--out", default=None)
    s.add_argument("--seed", type=int, default=1)
    s.add_argument("--mu", type=float, default=0.25,
                   help="mixing: share of each vertex's edges that leave "
                        "its community")
    s.add_argument("--alpha", type=float, default=2.3,
                   help="powerlaw: degree exponent")
    s.add_argument("--overlap", type=float, default=0.05,
                   help="powerlaw: share of vertices in two communities")
    s.add_argument("--edge-factor", type=int, default=16,
                   help="powerlaw: mean directed degree")
    s.add_argument("--gamma", type=float, default=2.0,
                   help="lfr: degree exponent")
    s.add_argument("--beta", type=float, default=1.0,
                   help="lfr: community-size exponent")
    s.add_argument("--mean-degree", type=float, default=20,
                   help="lfr: mean degree <k>")
    s.add_argument("--max-degree", type=int, default=50,
                   help="lfr: maximum degree k_max")
    s.add_argument("--cmin", type=int, default=20,
                   help="lfr: smallest community")
    s.add_argument("--cmax", type=int, default=100,
                   help="lfr: largest community")
    s.add_argument("--bits64", action="store_true")
    s.add_argument("--no-truth", action="store_true",
                   help="skip the ground-truth file (large graphs)")
    s.add_argument("--churn", type=float, metavar="FRAC", default=0.0,
                   help="also emit a deterministic insert/delete churn "
                        "stream (<out>.churn.npz + provenance) deleting "
                        "FRAC of the undirected pairs per batch "
                        "(streaming warm-start A/B, ISSUE 17)")
    s.add_argument("--churn-batches", type=int, default=1)
    s.add_argument("--churn-seed", type=int, default=1)
    s.add_argument("--many", type=int, metavar="K", default=0,
                   help="emit K graphs <out>_<k>.vite on distinct "
                        "splitmix64 streams with ONE set-level "
                        "provenance file (serving benches/tests)")

    c = sub.add_parser("convert", help="convert SNAP/MTX/METIS to Vite")
    c.add_argument("input")
    c.add_argument("--out", required=True)
    c.add_argument("--format", default="auto",
                   choices=("auto",) + tuple(FORMATS))
    c.add_argument("--bits64", action="store_true")
    c.add_argument("--symmetrize", default="auto",
                   choices=["auto", "yes", "no"])
    c.add_argument("--relabel", default=None,
                   choices=[None, "auto", "none", "dense"])

    sub.add_parser("bench", help="hardened TEPS bench (extra args pass "
                                 "through; see bench --help)",
                   add_help=False)

    v = sub.add_parser("verify-golden", help="run clustering and check "
                                             "the golden envelope")
    v.add_argument("--dataset", required=True)
    v.add_argument("--config", default="default")
    v.add_argument("--file", required=True, help="Vite graph file")
    v.add_argument("--bits64", action="store_true")
    v.add_argument("--engine", default="auto")
    v.add_argument("--truth", default=None,
                   help="LFR ground-truth file (default: provenance's)")
    v.add_argument("--truth-zero-based", action="store_true")
    v.add_argument("--golden", default=DEFAULT_GOLDEN_PATH)
    v.add_argument("--update-golden", action="store_true")
    return p


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    # `bench` forwards its tail verbatim to the bench parser (which also
    # reads the historical BENCH_* env knobs).
    if argv and argv[0] == "bench":
        return _cmd_bench(None, argv[1:])
    args = build_parser().parse_args(argv)
    if args.cmd == "fetch":
        if not args.name and not args.list:
            raise SystemExit("fetch: dataset name required (or --list)")
        return _cmd_fetch(args)
    if args.cmd == "synth":
        return _cmd_synth(args)
    if args.cmd == "convert":
        return _cmd_convert(args)
    if args.cmd == "verify-golden":
        return _cmd_verify_golden(args)
    raise SystemExit(f"unknown command {args.cmd!r}")


if __name__ == "__main__":
    sys.exit(main())
