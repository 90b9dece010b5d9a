"""all_to_all launch-latency microbenchmark: bracket the exchange cutover.

The exchange='auto' cutover (driver.AUTO_SPARSE_MIN_VERTICES) decides when
the sparse ghost plan replaces the replicated exchange.  Its comment keeps
making a LAUNCH-LATENCY argument ("per-launch latency charges per
collective on real ICI") that no tool of this repo had ever measured
(VERDICT r5 weak #3 / next #9).  This microbenchmark measures the three
collective patterns the two exchanges are made of, on the mesh it is run
on, and prints the honest bracket:

  all_gather(n)  — the replicated exchange's community pull (plus two
                   psum'd tables of the same extent => ~3 launches of
                   O(nv_total) bytes per chip per iteration);
  psum(n)        — the replicated tables' reduction;
  all_to_all(b)  — the sparse exchange's transport (3 launches per
                   iteration after the round-3 packing, pinned by
                   test_sparse_step_lowers_to_three_all_to_all; each moves
                   O(ghosts + S*budget) elements, ~ghost_frac * nv).

Per size: jitted shard_map'd op, warm-up call, then min-of-R wall times
(min, not mean: scheduler noise only ever ADDS).  The launch latency is
the time of the smallest size (bandwidth term ~0); the crossover bracket
is the nv span where 3 modeled sparse launches become cheaper than 3
modeled replicated launches.  On a virtual CPU mesh the numbers describe
THIS host (shared-memory "collectives", compute-bound — see the
BASELINE.md round-7 note); on a real TPU slice they describe ICI, which
is the measurement the cutover comment actually wants.  Either way the
tool prints a machine-readable JSON line so the bracket can be cited.

Two-axis mode (``--mesh DCNxICI``, ISSUE 18): the same ladder measured
per axis of the hybrid mesh the two-level exchange runs on — the
intra-ICI all_gather/psum that materializes the group community tables
vs the cross-DCN all_to_all that moves the sparse ghosts.  On a real
slice the ICI axis is the fast fabric and the DCN axis the slow one, so
the per-axis launch latencies are the two constants the two-level
design trades against each other; on a virtual CPU mesh both axes are
the same host and the split only proves the harness.

Usage:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/exchange_latency.py --devices 8
    python tools/exchange_latency.py --devices 8 --ghost-frac 0.1 --json
    python tools/exchange_latency.py --mesh 2x4 --json --out lat.json
"""

import argparse
import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_argparser():
    ap = argparse.ArgumentParser(
        description="all_to_all / all_gather launch-latency microbenchmark")
    ap.add_argument("--devices", type=int, default=8,
                    help="mesh size (virtual CPU devices are forced when "
                         "the backend is cpu and XLA_FLAGS doesn't already "
                         "ask for them)")
    ap.add_argument("--repeats", type=int, default=30,
                    help="timed calls per size (min is reported)")
    ap.add_argument("--min-log2", type=int, default=7,
                    help="smallest per-chip element count, log2")
    ap.add_argument("--max-log2", type=int, default=22,
                    help="largest per-chip element count, log2")
    ap.add_argument("--ghost-frac", type=float, default=0.10,
                    help="modeled ghost+budget fraction of nv for the "
                         "sparse side (scale-free; rmat partitions measure "
                         "0.05-0.2 per shard)")
    ap.add_argument("--mesh", metavar="DCNxICI", default=None,
                    help="two-axis mode: measure each collective per "
                         "hybrid-mesh axis (intra-ICI table gather vs "
                         "cross-DCN ghost all_to_all) instead of the flat "
                         "1-D ladder")
    ap.add_argument("--json", action="store_true",
                    help="emit one machine-readable JSON line at the end")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="also write the JSON verdict to FILE (the ladder's "
                         "stage L checkpoints through this)")
    return ap


def _emit(verdict, args):
    if args.out:
        with open(args.out, "w") as f:
            json.dump(verdict, f, indent=2, sort_keys=True)
            f.write("\n")
    if args.json:
        print(json.dumps(verdict))


def _two_axis(args, shape, plat) -> int:
    """Per-axis ladder on the hybrid (dcn, ici) mesh: the intra-ICI
    collectives that materialize the two-level exchange's group tables
    (all_gather + psum over the fast submesh) vs the cross-DCN
    all_to_all that moves its sparse ghosts, plus the both-axes global
    gather the scheme exists to avoid.  The per-axis launch latencies
    are the constants the two-level trade rests on."""
    import functools
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from cuvite_tpu.comm.mesh import (
        DCN_AXIS,
        ICI_AXIS,
        make_hybrid_mesh,
    )

    n_dcn, n_ici = shape
    S = n_dcn * n_ici
    mesh = make_hybrid_mesh(n_dcn, n_ici)
    spec = P((DCN_AXIS, ICI_AXIS))

    def timed(fn, arr):
        jax.block_until_ready(fn(arr))
        best = float("inf")
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(arr))
            best = min(best, time.perf_counter() - t0)
        return best

    def wrap(body, out=P()):
        return jax.jit(functools.partial(
            jax.shard_map, mesh=mesh, in_specs=spec, out_specs=out,
            check_vma=False)(body))

    @functools.lru_cache(maxsize=None)
    def ops():
        def ag_ici(x):
            return jax.lax.all_gather(x, ICI_AXIS, tiled=True)  # graftlint: replicated-ok=scope=bench; launch-latency microbenchmark measuring the ICI table gather itself

        def ps_ici(x):
            return jax.lax.psum(x, ICI_AXIS)  # graftlint: replicated-ok=scope=bench; same microbenchmark, psum arm

        def ag_glob(x):
            return jax.lax.all_gather(x, (DCN_AXIS, ICI_AXIS), tiled=True)  # graftlint: replicated-ok=scope=bench; the global gather the two-level exchange avoids — measured to cite the cost

        def a2a_dcn(x):
            return jax.lax.all_to_all(x, DCN_AXIS, 0, 0, tiled=True)

        return (wrap(ag_ici), wrap(ps_ici), wrap(ag_glob),
                wrap(a2a_dcn, out=spec))

    ag_i, ps_i, ag_g, a2a_d = ops()
    rows = []
    print(f"# hybrid mesh: {n_dcn}x{n_ici} {plat} (dcn x ici); per-chip "
          f"elements n; times are min-of-{args.repeats} wall seconds",
          flush=True)
    print(f"# {'n/chip':>10} {'ag(ici)':>12} {'psum(ici)':>12} "
          f"{'ag(global)':>12} {'a2a(dcn)':>12}")
    for k in range(args.min_log2, args.max_log2 + 1):
        n = 1 << k
        x = jnp.asarray(np.ones(S * n, dtype=np.float32))
        t_agi = timed(ag_i, x)
        t_psi = timed(ps_i, x)
        t_agg = timed(ag_g, x)
        b = max(n // n_dcn, 1)
        y = jnp.asarray(np.ones(S * n_dcn * b, dtype=np.float32))
        t_aad = timed(a2a_d, y)
        rows.append({"n_per_chip": n, "all_gather_ici_s": t_agi,
                     "psum_ici_s": t_psi, "all_gather_global_s": t_agg,
                     "all_to_all_dcn_s": t_aad})
        print(f"  {n:>10} {t_agi:>12.3e} {t_psi:>12.3e} {t_agg:>12.3e} "
              f"{t_aad:>12.3e}", flush=True)

    lat = {k: rows[0][k] for k in ("all_gather_ici_s", "psum_ici_s",
                                   "all_gather_global_s",
                                   "all_to_all_dcn_s")}
    print(f"# per-axis launch latency (smallest size): "
          f"ag(ici) {lat['all_gather_ici_s']*1e6:.0f}us, "
          f"psum(ici) {lat['psum_ici_s']*1e6:.0f}us, "
          f"ag(global) {lat['all_gather_global_s']*1e6:.0f}us, "
          f"a2a(dcn) {lat['all_to_all_dcn_s']*1e6:.0f}us")
    # The two-level per-iteration transport at the largest measured
    # per-chip count: 2 ICI gathers build the group tables (comm +
    # vdeg at the nv/|dcn| window) + 3 DCN all_to_alls move the ghosts
    # (~ghost_frac of the window); the flat alternative pays the global
    # gather + 2 global psums at the full nv window.
    last = rows[-1]
    t_two = (2.0 * last["all_gather_ici_s"]
             + 3.0 * last["all_to_all_dcn_s"] * args.ghost_frac)
    t_flat = (last["all_gather_global_s"] + 2.0 * last["psum_ici_s"]
              * n_dcn)
    print(f"# modeled per-iteration transport at n/chip="
          f"{last['n_per_chip']} (ghost_frac={args.ghost_frac}): "
          f"two-level {t_two:.3e}s vs flat-replicated {t_flat:.3e}s")
    verdict = {
        "platform": plat, "mesh": f"{n_dcn}x{n_ici}", "devices": S,
        "ghost_frac": args.ghost_frac,
        "launch_latency_s": lat,
        "rows": rows,
        "modeled_iteration_s": {"twolevel": t_two,
                                "flat_replicated": t_flat},
        "note": ("per-axis collective ladder on the hybrid mesh; on a "
                 "virtual CPU mesh both axes are the same host — the "
                 "split is meaningful on real ICI/DCN fabric only"),
    }
    _emit(verdict, args)
    return 0


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)
    shape = None
    if args.mesh:
        d_s, _, i_s = args.mesh.lower().replace("×", "x").partition("x")
        try:
            shape = (int(d_s), int(i_s or 1))
        except ValueError:
            raise SystemExit(f"--mesh must be DCNxICI (e.g. 2x4), "
                             f"got {args.mesh!r}")
        if shape[0] < 1 or shape[1] < 1:
            raise SystemExit("--mesh factors must be >= 1")
        args.devices = shape[0] * shape[1]
    if "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from cuvite_tpu.comm.mesh import VERTEX_AXIS, make_mesh

    S = args.devices
    plat = jax.devices()[0].platform

    if shape is not None:
        return _two_axis(args, shape, plat)

    mesh = make_mesh(S)

    def timed(fn, arr):
        out = fn(arr)
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(arr))
            best = min(best, time.perf_counter() - t0)
        return best

    @functools.lru_cache(maxsize=None)
    def ag_fn():
        @jax.jit
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(VERTEX_AXIS),
                           out_specs=P(), check_vma=False)
        def ag(x):
            return jax.lax.all_gather(x, VERTEX_AXIS, tiled=True)  # graftlint: replicated-ok=scope=bench; launch-latency microbenchmark measuring this collective itself, not a product table
        return ag

    @functools.lru_cache(maxsize=None)
    def psum_fn():
        @jax.jit
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(VERTEX_AXIS),
                           out_specs=P(), check_vma=False)
        def ps(x):
            return jax.lax.psum(x, VERTEX_AXIS)
        return ps

    @functools.lru_cache(maxsize=None)
    def a2a_fn():
        @jax.jit
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(VERTEX_AXIS),
                           out_specs=P(VERTEX_AXIS), check_vma=False)
        def a2a(x):
            return jax.lax.all_to_all(x, VERTEX_AXIS, 0, 0, tiled=True)
        return a2a

    rows = []
    print(f"# mesh: {S}x {plat}; per-chip elements n; times are "
          f"min-of-{args.repeats} wall seconds", flush=True)
    print(f"# {'n/chip':>10} {'all_gather':>12} {'psum':>12} "
          f"{'all_to_all':>12}")
    for k in range(args.min_log2, args.max_log2 + 1):
        n = 1 << k
        x = jnp.asarray(np.ones(S * n, dtype=np.float32))
        t_ag = timed(ag_fn(), x)
        t_ps = timed(psum_fn(), x)
        # all_to_all: same per-chip byte count, [S, n/S]-blocked transport
        # (pad so every pair block is nonempty).
        b = max(n // S, 1)
        y = jnp.asarray(np.ones((S * S, b), dtype=np.float32))
        t_aa = timed(a2a_fn(), y)
        rows.append({"n_per_chip": n, "all_gather_s": t_ag,
                     "psum_s": t_ps, "all_to_all_s": t_aa})
        print(f"  {n:>10} {t_ag:>12.3e} {t_ps:>12.3e} {t_aa:>12.3e}",
              flush=True)

    # Launch latency: the smallest size's time, where the bandwidth term
    # is negligible (a few hundred bytes/chip).
    lat = {k: rows[0][k] for k in ("all_gather_s", "psum_s",
                                   "all_to_all_s")}

    def interp(series, n):
        """Piecewise-linear read of a measured curve at per-chip count n
        (clamped; log-domain interpolation between the pow2 samples)."""
        pts = [(r["n_per_chip"], r[series]) for r in rows]
        if n <= pts[0][0]:
            return pts[0][1]
        for (n0, t0), (n1, t1) in zip(pts, pts[1:]):
            if n <= n1:
                f = (np.log2(n) - np.log2(n0)) / (np.log2(n1) - np.log2(n0))
                return t0 + f * (t1 - t0)
        return pts[-1][1]

    # Per-iteration exchange COLLECTIVE model over padded total vertex
    # count nv (transport only — the sparse env's extra per-iteration
    # sort/route compute is deliberately out of scope, it is what
    # tools/exchange_bench.py end-to-ends):
    #   replicated: 3 launches of nv elements per chip
    #     (all_gather(comm) + psum(comm_deg) + psum(comm_size))
    #   sparse:     3 all_to_all launches of ~ghost_frac * nv per chip
    #     (the packed ghost pull + owner-route fwd + reply; ghost_frac is
    #     per-shard ghosts+budget over TOTAL nv)
    print(f"# modeled per-iteration exchange transport "
          f"(ghost_frac={args.ghost_frac}):")
    print(f"# {'nv_total':>12} {'replicated':>12} {'sparse':>12}")
    model = []
    for k in range(args.min_log2 + 3, args.max_log2 + int(np.log2(S)) + 1):
        nv = 1 << k
        t_rep = (interp("all_gather_s", nv)
                 + 2.0 * interp("psum_s", nv))
        t_sp = 3.0 * interp("all_to_all_s",
                            max(int(args.ghost_frac * nv), 1))
        model.append((nv, t_rep, t_sp))
        print(f"  {nv:>12} {t_rep:>12.3e} {t_sp:>12.3e}")
    first_win = next((i for i, (_, tr, ts) in enumerate(model) if ts < tr),
                     None)
    if first_win is None:
        lo = hi = None
    elif first_win == 0:
        lo, hi = None, model[0][0]   # sparse wins at/below the range floor
    else:
        lo, hi = model[first_win - 1][0], model[first_win][0]
    verdict = {
        "platform": plat, "devices": S, "ghost_frac": args.ghost_frac,
        "launch_latency_s": lat,
        "crossover_bracket_nv": [lo, hi],
        "note": ("transport-only model; launch latencies from the "
                 "smallest measured size"),
    }
    print(f"# launch latency (smallest size): "
          f"all_gather {lat['all_gather_s']*1e6:.0f}us, "
          f"psum {lat['psum_s']*1e6:.0f}us, "
          f"all_to_all {lat['all_to_all_s']*1e6:.0f}us")
    if first_win is None:
        print("# crossover: NOT reached — the 3 replicated launches stay "
              "cheaper over the whole modeled range; the cutover remains "
              "the MEMORY bound (driver.AUTO_SPARSE_MIN_VERTICES)")
    elif first_win == 0:
        print(f"# crossover: at or below nv={hi} (sparse transport already "
              f"cheaper at the range floor) — the collective model does "
              f"NOT bind the cutover; the HBM bound does")
    else:
        print(f"# crossover bracket: nv in [{lo}, {hi}]")
    _emit(verdict, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
