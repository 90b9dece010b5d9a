#!/usr/bin/env python
"""Serving saturation load tool (ISSUE 11; `mix` added by ISSUE 20).

Four subcommands around the open-loop generator (serve/loadgen.py):

    # geometric arrival-rate ramp: find max sustainable jobs/s at the SLO
    python tools/serve_load.py sweep --b-max 8 --edges 1024 --slo-ms 500

    # THE acceptance A/B: 2x the measured saturation rate, admission on
    # (wait_p95 holds, excess rejected with retry_after_s) vs admission
    # off (unbounded wait growth); two schema-v4 bench records emitted
    python tools/serve_load.py ab --b-max 8 --out-prefix tools/logs/serve_r13

    # drive a SPAWNED `python -m cuvite_tpu.serve daemon` over its
    # socket at a fixed rate, then SIGTERM it and check the clean drain
    python tools/serve_load.py daemon --b-max 8 --rate 20 --jobs 64

    # skewed-mix packing A/B (ISSUE 20): 90:10 small:big open-loop mix,
    # per-class queues (merge_packing off) vs sub-row packing (on);
    # two schema-v5 records with a `mix` block; acceptance = packed
    # wins goodput AND small-class wait_p95 with merged_batches > 0
    python tools/serve_load.py mix --rate 20 --out-prefix tools/logs/mix_r20

`sweep`/`ab` run in-process (records via workloads.bench.run_serve_bench,
gated like-for-like by tools/perf_regress.py); `daemon` exercises the
full socket intake + dispatcher + SIGTERM drain path and emits a
compact JSON row (goodput, wait_p95 vs SLO, reject/shed counts, daemon
exit code) — the SLO row the first platform=tpu serving record needs.
"""

from __future__ import annotations

import argparse
import json
import os
import select
import signal
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _setup_jax(host_devices: int) -> None:
    from cuvite_tpu.utils.envknob import request_host_devices

    request_host_devices(host_devices)
    from cuvite_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()


def _warm_rungs(graphs, b_max: int, engine: str):
    """Compile every rung <= b_max once (open-loop partials can pad to
    any of them) with the job-set-pinned geometry; returns (cls, shape).
    The policy lives in ONE place — workloads.bench.warm_serve_rungs —
    shared with run_serve_bench so the two paths cannot drift."""
    from cuvite_tpu.workloads.bench import warm_serve_rungs

    return warm_serve_rungs(graphs, b_max, engine)


def _sweep_run(args):
    """Shared sweep machinery for `sweep`/`ab` (one copy so the
    setup/warm/pin policy cannot drift): synthesize the job set, warm
    the rungs, ramp rates printing a row per round.  Returns
    ``(graphs, make_server, reports, best)``; ``best is None`` means
    even the start rate overloads (callers bail with rc=1)."""
    _setup_jax(args.host_devices)
    from cuvite_tpu.serve import AdmissionConfig, LouvainServer, ServeConfig
    from cuvite_tpu.serve.loadgen import saturation_sweep
    from cuvite_tpu.workloads.synth import many_seed, synthesize_graph

    graphs = [synthesize_graph(args.edges, seed=many_seed(args.seed, k))
              for k in range(args.jobs)]
    cls, shape = _warm_rungs(graphs, args.b_max, args.engine)

    def make_server():
        srv = LouvainServer(ServeConfig(
            b_max=args.b_max, linger_s=args.linger_ms / 1e3,
            engine=args.engine,
            admission=AdmissionConfig(wait_slo_s=args.slo_ms / 1e3)))
        if shape is not None:
            srv.pin_shape(cls, shape)
        return srv

    reports, best = saturation_sweep(
        make_server, lambda: graphs, start_rate=args.start_rate,
        slo_s=args.slo_ms / 1e3, growth=args.growth,
        max_rounds=args.max_rounds,
        pipelined=getattr(args, "pipeline", "off") == "on")
    for rep in reports:
        print(json.dumps(rep.row()))
    if best is None:
        print(f"# even {args.start_rate} jobs/s overloads; lower "
              "--start-rate", file=sys.stderr)
    return graphs, make_server, reports, best


def cmd_sweep(args) -> int:
    _graphs, _mk, _reports, best = _sweep_run(args)
    if best is None:
        return 1
    print(json.dumps({"saturation_jobs_per_s": round(best.rate, 3),
                      "wait_p95_ms": round(best.wait_p95_s * 1e3, 3),
                      "slo_ms": args.slo_ms}))
    return 0


def cmd_ab(args) -> int:
    """Sweep, then 2x saturation with admission on vs off; both records
    written (BASELINE.md round-13 wants exactly this pair)."""
    from cuvite_tpu.workloads.bench import run_serve_bench, validate_record

    _graphs, _mk, reports, best = _sweep_run(args)
    if best is None:
        return 1
    # Measured saturation = the highest GOODPUT any sweep round
    # demonstrated, not the last sustainable offered rate: short sweep
    # bursts carry a fixed linger/drain tail that inflates wall and
    # biases the offered-rate knee low, so 2x the knee can land under
    # the queue's true capacity and never actually overload it.
    sat = max(best.rate, *(r.goodput_jobs_per_s for r in reports))
    rate2x = 2.0 * sat
    print(json.dumps({"saturation_jobs_per_s": round(sat, 3),
                      "sustainable_offered_rate": round(best.rate, 3),
                      "overload_rate": round(rate2x, 3)}))
    out = {}
    for arm in (True, False):
        rec = run_serve_bench(
            rate=rate2x, b_max=args.b_max, edges=args.edges,
            n_jobs=args.ab_jobs, seed=args.seed, slo_ms=args.slo_ms,
            admission=arm, linger_ms=args.linger_ms,
            engine=args.engine, platform=args.platform,
            budget_s=args.budget,
            pipelined=args.pipeline == "on")
        problems = validate_record(rec)
        if problems:
            print(f"# invalid record ({arm=}): {problems}",
                  file=sys.stderr)
            return 2
        out[arm] = rec
        line = json.dumps(rec)
        print(line)
        if args.out_prefix:
            suffix = "admit" if arm else "noadmit"
            path = f"{args.out_prefix}_{suffix}.json"
            with open(path, "w", encoding="utf-8") as f:
                f.write(line + "\n")
            print(f"# wrote {path}", file=sys.stderr)
    on, off = out[True]["serve"], out[False]["serve"]
    verdict = {
        "overload_rate": round(rate2x, 3),
        "admit_wait_p95_ms": on["wait_p95_ms"],
        "admit_slo_met": on["slo_met"],
        "admit_reject_rate": on["reject_rate"],
        "noadmit_wait_p95_ms": off["wait_p95_ms"],
        "noadmit_slo_met": off["slo_met"],
        "acceptance": bool(on["slo_met"] and on["reject_rate"] > 0
                           and not off["slo_met"]),
    }
    print(json.dumps({"verdict": verdict}))
    return 0 if verdict["acceptance"] else 1


def cmd_pipeab(args) -> int:
    """THE ISSUE-14 acceptance A/B: pipelined vs serial dispatcher on
    the SAME seeded job set at the same saturating offered rate
    (admission off, so goodput == measured capacity, not an intake
    policy).  Emits one schema-v4 serve record per arm (separated by
    serve.pipelined in perf_regress) and a verdict line with the
    speedup + the measured pack_s/device_s ratio the acceptance
    criterion is conditioned on (overlap can only buy up to
    (pack+device)/max(pack, device))."""
    from cuvite_tpu.workloads.bench import run_serve_bench, validate_record

    _graphs, _mk, reports, best = _sweep_run(args)
    if best is None:
        return 1
    sat = max(best.rate, *(r.goodput_jobs_per_s for r in reports))
    rate = args.overload_factor * sat
    print(json.dumps({"serial_saturation_jobs_per_s": round(sat, 3),
                      "ab_rate": round(rate, 3)}))
    out = {}
    for pipe in (False, True):
        rec = run_serve_bench(
            rate=rate, b_max=args.b_max, edges=args.edges,
            n_jobs=args.ab_jobs, seed=args.seed, slo_ms=args.slo_ms,
            admission=False, linger_ms=args.linger_ms,
            engine=args.engine, platform=args.platform,
            budget_s=args.budget, pipelined=pipe)
        problems = validate_record(rec)
        if problems:
            print(f"# invalid record (pipelined={pipe}): {problems}",
                  file=sys.stderr)
            return 2
        out[pipe] = rec
        line = json.dumps(rec)
        print(line)
        if args.out_prefix:
            suffix = "pipelined" if pipe else "serial"
            path = f"{args.out_prefix}_{suffix}.json"
            with open(path, "w", encoding="utf-8") as f:
                f.write(line + "\n")
            print(f"# wrote {path}", file=sys.stderr)
    ser, pip = out[False]["serve"], out[True]["serve"]
    speedup = pip["goodput_jobs_per_s"] / max(ser["goodput_jobs_per_s"],
                                              1e-9)
    ratio = ser["pack_s"] / max(ser["device_s"], 1e-9)
    verdict = {
        "serial_goodput_jobs_per_s": ser["goodput_jobs_per_s"],
        "pipelined_goodput_jobs_per_s": pip["goodput_jobs_per_s"],
        "speedup": round(speedup, 3),
        "pack_over_device": round(ratio, 3),
        "overlap_frac": pip.get("overlap_frac"),
        # The conditional acceptance form (ISSUE 14): >= 1.25x is
        # demanded only when pack is at least half of device — below
        # that, perfect overlap cannot reach 1.25x arithmetically.
        "acceptance": bool(speedup >= 1.25 or ratio < 0.5),
    }
    print(json.dumps({"verdict": verdict}))
    return 0 if verdict["acceptance"] else 1


def cmd_mix(args) -> int:
    """THE ISSUE-20 acceptance A/B: one 90:10 skewed small:big arrival
    mix at the same offered rate, served twice — merge_packing on
    (small bins pack as fenced sub-rows of the big class's program) vs
    off (strict per-class queues).  Two schema-v5 records with the
    ``mix`` block; the verdict demands the packed arm beat the
    per-class arm on BOTH total goodput and small-class wait_p95 at
    the equal SLO."""
    _setup_jax(args.host_devices)
    from cuvite_tpu.workloads.bench import (
        run_mixed_serve_bench,
        validate_record,
    )

    out = {}
    for packed in (False, True):
        rec = run_mixed_serve_bench(
            rate=args.rate, merge_packing=packed, b_max=args.b_max,
            small_edges=args.edges, big_scale=args.big_scale,
            big_edge_factor=args.big_edge_factor,
            n_small=args.n_small, n_big=args.n_big, seed=args.seed,
            slo_ms=args.slo_ms, linger_ms=args.linger_ms,
            engine=args.engine, platform=args.platform,
            budget_s=args.budget, pipelined=args.pipeline == "on")
        problems = validate_record(rec)
        if problems:
            print(f"# invalid record (merge_packing={packed}): {problems}",
                  file=sys.stderr)
            return 2
        out[packed] = rec
        line = json.dumps(rec)
        print(line)
        if args.out_prefix:
            suffix = "packed" if packed else "perclass"
            path = f"{args.out_prefix}_{suffix}.json"
            with open(path, "w", encoding="utf-8") as f:
                f.write(line + "\n")
            print(f"# wrote {path}", file=sys.stderr)
    plain, packed = out[False], out[True]
    ps, pl = packed["serve"], plain["serve"]
    pm, lm = packed["mix"], plain["mix"]
    verdict = {
        "rate_jobs_per_s": round(args.rate, 3),
        "perclass_goodput_jobs_per_s": pl["goodput_jobs_per_s"],
        "packed_goodput_jobs_per_s": ps["goodput_jobs_per_s"],
        "perclass_small_wait_p95_ms": lm["small_wait_p95_ms"],
        "packed_small_wait_p95_ms": pm["small_wait_p95_ms"],
        "merged_batches": pm["merged_batches"],
        "packed_subrow_util": pm["subrow_util"],
        "acceptance": bool(
            ps["goodput_jobs_per_s"] >= pl["goodput_jobs_per_s"]
            and pm["small_wait_p95_ms"] <= lm["small_wait_p95_ms"]
            and pm["merged_batches"] > 0),
    }
    print(json.dumps({"verdict": verdict}))
    return 0 if verdict["acceptance"] else 1


def _read_ready(proc, timeout_s: float) -> dict:
    """The daemon's readiness line, with a hard deadline (a wedged
    backend init must fail this tool, not hang it)."""
    deadline = time.monotonic() + timeout_s
    buf = ""
    while time.monotonic() < deadline:
        r, _, _ = select.select([proc.stdout], [], [], 0.5)
        if not r:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"daemon exited rc={proc.returncode} before ready")
            continue
        chunk = proc.stdout.readline()
        if not chunk:
            raise RuntimeError("daemon stdout closed before ready")
        buf = chunk.strip()
        if buf.startswith("{"):
            msg = json.loads(buf)
            if "ready" in msg:
                return msg["ready"]
    raise RuntimeError(f"daemon not ready within {timeout_s}s")


def cmd_daemon(args) -> int:
    """Spawn the daemon, drive an open-loop synth load over its socket,
    SIGTERM it, and verify the graceful drain (exit 0 + summary).  This
    parent never imports JAX: the device belongs to the daemon child."""
    cmd = [sys.executable, "-m", "cuvite_tpu.serve", "daemon",
           "--port", "0", "--b-max", str(args.b_max),
           "--linger-ms", str(args.linger_ms),
           "--engine", args.engine,
           "--pipeline", args.pipeline,
           "--host-devices", str(args.host_devices)]
    if args.slo_ms > 0:
        cmd += ["--wait-slo-ms", str(args.slo_ms)]
    if args.fault_plan:
        cmd += ["--fault-plan", args.fault_plan]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True,
                            cwd=REPO)
    try:
        ready = _read_ready(proc, args.ready_timeout)
        port = ready["port"]
        # Loopback to the daemon this tool just spawned, not a fetch.
        conn = socket.create_connection(  # graftlint: disable=R009 — localhost control channel to our own child process
            ("127.0.0.1", port), timeout=30.0)
        lines = conn.makefile("r", encoding="utf-8")
        events = {"result": 0, "failed": 0, "shed": 0, "rejected": 0,
                  "acked": 0, "refused": 0, "summary": None}
        done_evt = threading.Event()

        def reader():
            for line in lines:
                try:
                    msg = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if "serve_summary" in msg:
                    events["summary"] = msg["serve_summary"]
                    done_evt.set()
                elif "result" in msg:
                    events["result"] += 1
                elif "failed" in msg:
                    events["failed"] += 1
                elif "shed" in msg:
                    events["shed"] += 1
                elif msg.get("rejected"):
                    events["rejected"] += 1
                elif "ok" in msg:
                    events["acked" if msg["ok"] else "refused"] += 1
            done_evt.set()

        threading.Thread(target=reader, daemon=True).start()
        t0 = time.perf_counter()
        wlock = threading.Lock()
        for k in range(args.jobs):
            target = t0 + k / args.rate
            now = time.perf_counter()
            if target > now:
                time.sleep(target - now)
            req = {"op": "submit", "synth": {"edges": args.edges,
                                             "seed": 1000 + k},
                   "tenant": f"t{k % max(args.tenants, 1)}"}
            if args.deadline_ms:
                req["deadline_s"] = args.deadline_ms / 1e3
            with wlock:
                conn.sendall((json.dumps(req) + "\n").encode())
        # Submits are pipelined (no per-request round trip); wait until
        # the daemon has ANSWERED every one before pulling the trigger,
        # or the SIGTERM would drain-refuse intake it never saw.
        ack_deadline = time.monotonic() + args.ready_timeout
        while time.monotonic() < ack_deadline:
            if (events["acked"] + events["rejected"]
                    + events["refused"]) >= args.jobs:
                break
            time.sleep(0.05)
        # Graceful shutdown via the signal path (the acceptance check).
        proc.send_signal(signal.SIGTERM)
        done_evt.wait(timeout=args.drain_timeout)
        rc = proc.wait(timeout=60)
        wall = time.perf_counter() - t0
        summary = events["summary"] or {}
        stats = summary if "jobs_done" in summary else {}
        row = {
            "daemon": True,
            "b_max": args.b_max,
            "engine": args.engine,
            "pipelined": args.pipeline == "on",
            "arrival_jobs_per_s": round(args.rate, 3),
            "offered": args.jobs,
            "done": stats.get("jobs_done", events["result"]),
            "failed": stats.get("jobs_failed", events["failed"]),
            "shed": stats.get("jobs_shed", events["shed"]),
            "rejected": stats.get("jobs_rejected", events["rejected"]),
            "goodput_jobs_per_s": round(
                stats.get("jobs_done", events["result"]) / max(wall, 1e-9),
                3),
            "wait_p95_ms": stats.get("wait_p95_ms"),
            "slo_ms": args.slo_ms,
            "conservation": summary.get("conservation"),
            "daemon_rc": rc,
            "clean_drain": bool(rc == 0 and summary),
        }
        print(json.dumps(row))
        if args.out:
            with open(args.out, "w", encoding="utf-8") as f:
                f.write(json.dumps(row) + "\n")
        return 0 if row["clean_drain"] else 1
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python tools/serve_load.py",
        description="serving saturation load generator")
    sub = p.add_subparsers(dest="cmd", required=True)

    def common(q):
        q.add_argument("--b-max", type=int, default=8)
        q.add_argument("--edges", type=int, default=1024)
        q.add_argument("--jobs", type=int, default=64)
        q.add_argument("--seed", type=int, default=1)
        q.add_argument("--slo-ms", type=float, default=500.0)
        q.add_argument("--linger-ms", type=float, default=20.0)
        q.add_argument("--engine", default="bucketed",
                       choices=["bucketed", "fused"])
        q.add_argument("--host-devices", type=int, default=8)
        q.add_argument("--pipeline", default="off", choices=["on", "off"],
                       help="two-stage pipelined dispatch (ISSUE 14): "
                            "sweep/ab run the in-process dispatcher in "
                            "this mode; daemon forwards it to the "
                            "spawned daemon CLI")

    sw = sub.add_parser("sweep", help="find max sustainable jobs/s")
    common(sw)
    sw.add_argument("--start-rate", type=float, default=4.0)
    sw.add_argument("--growth", type=float, default=1.6)
    sw.add_argument("--max-rounds", type=int, default=8)

    ab = sub.add_parser("ab", help="2x-saturation admission on/off A/B")
    common(ab)
    ab.add_argument("--start-rate", type=float, default=4.0)
    ab.add_argument("--growth", type=float, default=1.5)
    ab.add_argument("--max-rounds", type=int, default=12)
    ab.add_argument("--ab-jobs", type=int, default=512,
                    help="job count for the two 2x-overload runs: must "
                         "offer enough WORK that the backlog a 2x rate "
                         "builds can push queue waits past the SLO "
                         "(64 jobs drain before the wait integral shows)")
    ab.add_argument("--platform", default="cpu")
    ab.add_argument("--budget", type=float, default=600.0)
    ab.add_argument("--out-prefix", default=None,
                    help="write <prefix>_admit.json / <prefix>_noadmit.json")

    pab = sub.add_parser("pipeab",
                         help="pipelined-vs-serial dispatcher A/B at a "
                              "saturating rate (ISSUE 14 acceptance)")
    common(pab)
    pab.add_argument("--start-rate", type=float, default=4.0)
    pab.add_argument("--growth", type=float, default=1.5)
    pab.add_argument("--max-rounds", type=int, default=12)
    pab.add_argument("--overload-factor", type=float, default=1.5,
                     help="offered rate = factor * measured serial "
                          "saturation (must exceed BOTH arms' capacity "
                          "so goodput reads capacity, not arrival)")
    pab.add_argument("--ab-jobs", type=int, default=256)
    pab.add_argument("--platform", default="cpu")
    pab.add_argument("--budget", type=float, default=600.0)
    pab.add_argument("--out-prefix", default=None,
                     help="write <prefix>_serial.json / "
                          "<prefix>_pipelined.json")

    mx = sub.add_parser("mix",
                        help="90:10 skewed-mix packed-vs-per-class A/B "
                             "(ISSUE 20 acceptance)")
    common(mx)
    mx.add_argument("--mix", default="90:10",
                    help="small:big arrival ratio by count (informational"
                         " — pool sizes come from --n-small/--n-big; the "
                         "default pools realize 90:10)")
    mx.add_argument("--rate", type=float, default=20.0,
                    help="offered arrival rate over the WHOLE mix")
    mx.add_argument("--big-scale", type=int, default=13,
                    help="R-MAT scale of the big pool (default 13 with "
                         "--big-edge-factor 2 lands in (8192, 32768), an "
                         "n_sub=2 row class for 1024-edge smalls)")
    mx.add_argument("--big-edge-factor", type=int, default=2)
    mx.add_argument("--n-small", type=int, default=None)
    mx.add_argument("--n-big", type=int, default=None)
    mx.add_argument("--platform", default="cpu")
    mx.add_argument("--budget", type=float, default=600.0)
    mx.add_argument("--out-prefix", default=None,
                    help="write <prefix>_packed.json / "
                         "<prefix>_perclass.json")
    # The packed program is plan-free (fused-style specs); defaulting
    # the PLAIN arm to bucketed would measure the ISSUE-10 engine gap,
    # not the packing policy — the A/B runs fused on both arms unless
    # explicitly overridden.
    mx.set_defaults(engine="fused")

    dm = sub.add_parser("daemon",
                        help="drive a spawned serve daemon over its socket")
    common(dm)
    dm.add_argument("--rate", type=float, default=10.0)
    dm.add_argument("--tenants", type=int, default=4)
    dm.add_argument("--deadline-ms", type=float, default=None)
    dm.add_argument("--fault-plan", default=None)
    dm.add_argument("--ready-timeout", type=float, default=180.0)
    dm.add_argument("--drain-timeout", type=float, default=600.0)
    dm.add_argument("--out", default=None)
    return p


def main(argv=None) -> int:
    args = _build_parser().parse_args(argv)
    if args.cmd == "sweep":
        return cmd_sweep(args)
    if args.cmd == "ab":
        return cmd_ab(args)
    if args.cmd == "pipeab":
        return cmd_pipeab(args)
    if args.cmd == "mix":
        return cmd_mix(args)
    return cmd_daemon(args)


if __name__ == "__main__":
    sys.exit(main())
