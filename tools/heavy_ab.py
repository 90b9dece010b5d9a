"""Heavy-class + segmented-coalesce A/B: the two kernel-vs-sort decision
measurements of ISSUE 8 (cf. heavy_kernel_design.md's decision rule).

Sweep 1 (heavy rows): community-range-tile Pallas kernel vs the XLA
sorted path on hub rows.  The kernel's cost is O(D * nv_ceil / C)
matmul passes per row — linear in the COMMUNITY-SPACE size — while the
sort path is O(D log^2 D) per row regardless of nv.  The sweep times
both over (D, nv_ceil) so the log records where the tile kernel wins.

Sweep 2 (seg-coalesce, `python tools/heavy_ab.py seg`): the coalesce
engines vs the packed-sort chokepoint on relabeled-slab workloads, per
slab class — the dense 'xla' engine (kernels/seg_coalesce.py) on its
budget-eligible classes, plus the
ISSUE-19 big-class arms on every class: 'msd' (two-pass int32 MSD
src-partition sort) and 'hash' (hash-slot accumulate with device-side
collision detection + sort retry).  The nv_pad >= 2^16 classes are the
ones the round-10 baseline showed paying the 64-bit variadic
comparator tax — the msd/hash cells there are the ISSUE-19 acceptance
measurement.  Every cell asserts bit-identity vs the sort oracle
before timing.  Appends to tools/logs/seg_coalesce_ab_r19.log.

Usage:
    python tools/heavy_ab.py                   # both sweeps (chip)
    python tools/heavy_ab.py heavy|seg         # one sweep
    AB_REPEATS=1 python tools/heavy_ab.py heavy 10240:128:8388608  # 1 case
    JAX_PLATFORMS=cpu python tools/heavy_ab.py   # interpret-mode smoke

Appends dated blocks to tools/logs/heavy_ab_r5.log (heavy) and
tools/logs/seg_coalesce_ab_r10.log (coalesce).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOG = os.path.join(REPO, "tools", "logs", "heavy_ab_r5.log")
SEG_LOG = os.path.join(REPO, "tools", "logs", "seg_coalesce_ab_r19.log")


def _log_to(path, msg):
    line = f"[{time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime())}] {msg}"
    print(line, flush=True)
    with open(path, "a") as f:
        f.write(line + "\n")


def log(msg):
    _log_to(LOG, msg)


def time_best(fn, n=5):
    fn()  # compile + warm
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def seg_coalesce_ab():
    """Sweep 2: dense coalesce engines vs the packed-sort chokepoint on
    synthetic relabeled slabs (dense ids < nv_pad, 20% tail padding,
    dyadic weights), per slab class.  Every cell also asserts the
    engines' outputs are bit-identical before timing them."""
    from cuvite_tpu.ops.segment import coalesced_runs

    plat = jax.default_backend()
    interpret = plat != "tpu"
    _log_to(SEG_LOG, f"seg-coalesce A/B start backend={plat} "
                     f"interpret={interpret}")
    rng = np.random.default_rng(11)
    for nv_pad, ne_pad in ((1024, 1 << 17), (4096, 1 << 18),
                           (4096, 1 << 20), (1 << 16, 1 << 20),
                           (1 << 18, 1 << 20)):
        # msd/hash run on EVERY class: on the small classes msd
        # delegates to the packed sort (expect ~1.0x, a delegation
        # check), on the nv_pad >= 2^16 classes they are the ISSUE-19
        # candidates against the 64-bit comparator tax.
        engines = ["sort", "msd", "hash"]
        if nv_pad <= 4096:
            # Dense classes (within the accumulator budget).
            engines.insert(1, "xla")
        engines = tuple(engines)
        n_real = ne_pad - ne_pad // 5
        src = np.full(ne_pad, nv_pad, np.int32)
        dst = np.zeros(ne_pad, np.int32)
        w = np.zeros(ne_pad, np.float32)
        src[:n_real] = rng.integers(0, nv_pad, n_real)
        dst[:n_real] = rng.integers(0, nv_pad, n_real)
        w[:n_real] = rng.integers(1, 64, n_real) / 8.0
        arrs = tuple(jnp.asarray(x) for x in (src, dst, w))

        # One jitted callable per engine (engine/nv_pad static via the
        # closure): every cell times a compiled program, none pays
        # eager per-op dispatch — apples-to-apples.
        def _jitted(eng):
            return jax.jit(lambda s, d, ww: coalesced_runs(
                s, d, ww, nv_pad=nv_pad, engine=eng))

        # One jitted callable per engine, reused for the parity check
        # AND the timing (a fresh jit wrapper would recompile sort for
        # the reference and again for its timed cell).
        runs = {eng: _jitted(eng) for eng in engines}
        ref = jax.device_get(runs["sort"](*arrs))
        times = {}
        for eng in engines:
            run = runs[eng]
            got = jax.device_get(run(*arrs))
            if not all(np.array_equal(r, g) for r, g in zip(ref, got)):
                # A wrong-result engine must never contribute a timing
                # the promotion decision could read: loud, and skipped.
                _log_to(SEG_LOG,
                        f"nv_pad={nv_pad} ne_pad={ne_pad}: {eng} "
                        f"FAILED bit-identity vs sort — NOT timed")
                continue
            t = time_best(lambda r=run: jax.block_until_ready(r(*arrs)))
            times[eng] = t
            _log_to(SEG_LOG,
                    f"nv_pad={nv_pad} ne_pad={ne_pad}: {eng} "
                    f"{t * 1e3:.1f} ms  vs sort "
                    f"{times[eng] / times['sort']:.2f}x")
    _log_to(SEG_LOG, "seg-coalesce A/B done")


# (D, H, nv_ceil) per case: D neighbor slots per hub, H hubs, nv_ceil
# the community range.  The last default case is the golden graph's
# phase 0 (powerlaw-1e8: 127 hubs of degree <= 9839, nv_pad 2^23).
DEFAULT_CASES = ((4096, 32, 8192), (4096, 32, 65536), (16384, 32, 1 << 20),
                 (10240, 128, 1 << 23))


def main(cases=DEFAULT_CASES, repeats=5):
    from cuvite_tpu.kernels.heavy_bincount import heavy_argmax_pallas
    from cuvite_tpu.louvain.bucketed import _row_argmax_sorted

    interpret = jax.default_backend() != "tpu"
    plat = jax.default_backend()
    log(f"heavy A/B start backend={plat} interpret={interpret}")
    rng = np.random.default_rng(7)
    for D, H, nv_ceil in cases:
        if interpret and (D, nv_ceil) != (4096, 8192):
            # Interpret mode executes the grid in Python — the big
            # cases would take hours; cpu is a correctness smoke only.
            continue
        nv = nv_ceil - 7
        cmat = rng.integers(0, nv, size=(H, D)).astype(np.int32)
        wmat = (rng.integers(1, 32, size=(H, D)) / 16.0).astype(np.float32)
        curr = rng.integers(0, nv, size=H).astype(np.int32)
        vdeg = wmat.sum(axis=1)
        sl = np.zeros(H, dtype=np.float32)
        comm_deg = (rng.integers(1, 256, size=nv_ceil) / 8.0).astype(
            np.float32)
        ax = comm_deg[curr] - vdeg
        const = jnp.asarray(np.float32(1.0 / vdeg.sum()))
        cT = jnp.asarray(np.ascontiguousarray(cmat.T))
        wT = jnp.asarray(np.ascontiguousarray(wmat.T))
        cd = jnp.asarray(comm_deg)
        cu, vd, slj, axj = map(jnp.asarray, (curr, vdeg, sl, ax))
        # XLA twin: the per-row sorted dedup (payloads as sort
        # operands), on identical rows.
        cm = jnp.asarray(cmat)
        wm = jnp.asarray(wmat)
        ay = jnp.asarray(comm_deg[cmat])
        out = {}

        def run_kernel():
            out["k"] = jax.block_until_ready(heavy_argmax_pallas(
                cT, wT, cd, cu, vd, slj, axj, const, interpret=interpret))

        def run_sorted():
            out["s"] = jax.block_until_ready(_row_argmax_sorted(
                cm, wm, ay, None, cu, vd, slj, axj, const,
                np.iinfo(np.int32).max))

        try:
            tk = time_best(run_kernel, repeats)
        except Exception as e:  # mosaic lowering can reject shapes
            log(f"D={D} nv_ceil={nv_ceil}: kernel FAILED {e!r:.200}")
            continue
        ts = time_best(run_sorted, repeats)
        # Semantic identity on the A/B inputs: best_c/counter0 must be
        # bitwise equal.  best_gain is compared to 1-2 ulp: const here
        # is 1/sum(w) (not a power of two like the unit tests use), so
        # XLA's FMA contraction rounds the gain's second term once
        # where the non-contracted form rounds twice — measured 1 ulp
        # on ~half the rows, never changing the argmax.
        bk, br = out["k"], out["s"]
        gk, gr = np.asarray(bk[1]), np.asarray(br.best_gain)
        fin = np.isfinite(gk) & np.isfinite(gr)
        same = (np.array_equal(np.asarray(bk[0]), np.asarray(br.best_c))
                and np.array_equal(fin, np.isfinite(gr))
                and np.allclose(gk[fin], gr[fin], rtol=3e-7, atol=0))
        log(f"D={D} nv_ceil={nv_ceil} H={H}: kernel {tk*1e3:.1f} ms  "
            f"sorted {ts*1e3:.1f} ms  ratio {tk/ts:.2f}x  "
            f"semantically_identical={same}")
    log("heavy A/B done")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "both"
    if which in ("heavy", "both"):
        # Optional D:H:NV_CEIL cases after the sweep name.
        cases = [tuple(int(x) for x in a.split(":")) for a in sys.argv[2:]]
        main(cases or DEFAULT_CASES,
             repeats=int(os.environ.get("AB_REPEATS", "5")))
    if which in ("seg", "both"):
        seg_coalesce_ab()
