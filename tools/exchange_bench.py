"""Sparse-vs-replicated exchange A/B on the virtual 8-device CPU mesh.

CPU-only by default (JAX_PLATFORMS=cpu unless set).  The parent only
spawns one child per config and never initializes a backend itself.

Re-measures the gap after the round-3 collective packing (7 all_to_all
per iteration -> 3, comm/exchange.py) — VERDICT r2 item 5.  The sparse
plan is a MEMORY play (O(owned+ghosts) per-chip state vs O(nv_total)); a
shrinking time gap is what makes the 2^26 auto-cutover
(driver.AUTO_SPARSE_MIN_VERTICES) safe.

Usage:
    python tools/exchange_bench.py            # scales 18 20
    AB_SCALES="18" python tools/exchange_bench.py
"""

import os
import subprocess
import sys
import time

# Virtual 8-device mesh: must precede jax backend init (see conftest.py).
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: F401,E402  (repo path + compile cache)

import jax  # noqa: E402

from cuvite_tpu.io.generate import generate_rmat  # noqa: E402
from cuvite_tpu.louvain.driver import louvain_phases  # noqa: E402


def _vm_hwm_mib():
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) // 1024
    except OSError:
        pass
    return -1


def run_one(scale: int, nsh: int, exchange: str):
    g = generate_rmat(scale, edge_factor=16, seed=1)
    # warm-up run eats compiles; timed run is steady-state
    louvain_phases(g, nshards=nsh, exchange=exchange)
    t0 = time.perf_counter()
    res = louvain_phases(g, nshards=nsh, exchange=exchange)
    wall = time.perf_counter() - t0
    print(f"scale={scale} exchange={exchange:10s} wall={wall:8.1f}s "
          f"Q={res.modularity:.5f} iters={res.total_iterations} "
          f"rss_hwm={_vm_hwm_mib()}MiB",
          flush=True)
    return wall


def main():
    scales = [int(s) for s in os.environ.get("AB_SCALES", "18 20").split()]
    nsh = int(os.environ.get("AB_SHARDS", "8"))
    # Parse ONCE, up front: a malformed value is reported and replaced
    # by the default BEFORE any child launches — not discovered as a
    # ValueError partway through a multi-hour sweep.
    try:
        child_timeout = float(os.environ.get("AB_CHILD_TIMEOUT") or 7200)
    except ValueError:
        print(f"# ignoring malformed AB_CHILD_TIMEOUT="
              f"{os.environ.get('AB_CHILD_TIMEOUT')!r}; using 7200s",
              flush=True)
        child_timeout = 7200.0
    one = os.environ.get("AB_EXCHANGE")  # subprocess mode: one config
    if one:
        # Only a child touches a backend: a parent holding the device
        # would leave its children none.
        print(f"# backend={jax.default_backend()} "
              f"devices={len(jax.devices())} shards={nsh}", flush=True)
        for scale in scales:
            run_one(scale, nsh, one)
        return
    for scale in scales:
        row = {}
        for exchange in ("replicated", "sparse"):
            # Per-config SUBPROCESS: independent RSS high-water (the
            # sparse plan's whole point is the memory footprint) and no
            # shared jit caches between the two configs.
            env = dict(os.environ, AB_SCALES=str(scale), AB_EXCHANGE=exchange,
                       AB_SHARDS=str(nsh))
            try:
                # Generous ceiling: the slowest measured config (sparse,
                # scale 22) ran ~16 min; the 2h default covers every
                # scale this host can hold plus cold-compile headroom,
                # while still unwedging an A/B run whose child hit a
                # pathological stall (TPU client handshake, OOM thrash).
                out = subprocess.run(
                    [sys.executable, os.path.abspath(__file__)],
                    env=env, capture_output=True, text=True,
                    timeout=child_timeout)
            except subprocess.TimeoutExpired as e:
                # Mirror the rc != 0 branch: a killed child must be LOUD,
                # not a silently missing row in the A/B table.
                tail = (e.stderr or b"")
                tail = tail.decode(errors="replace") \
                    if isinstance(tail, bytes) else tail
                print(f"scale={scale} exchange={exchange}: TIMEOUT after "
                      f"{e.timeout:.0f}s (child killed) {tail[-400:]}",
                      flush=True)
                continue
            if out.returncode != 0:
                # A child that OOMs/crashes after printing its header must
                # be LOUD, not reduced to its last stdout line.
                print(f"scale={scale} exchange={exchange}: "
                      f"rc={out.returncode} "
                      f"{(out.stderr or '')[-400:]}", flush=True)
            elif out.stdout.strip():
                print(out.stdout.strip().splitlines()[-1], flush=True)
            for line in out.stdout.splitlines():
                if line.startswith(f"scale={scale} exchange={exchange}"):
                    row[exchange] = float(line.split("wall=")[1].split("s")[0])
        if "replicated" in row and "sparse" in row:
            print(f"scale={scale} sparse/replicated = "
                  f"{row['sparse'] / row['replicated']:.2f}x", flush=True)


if __name__ == "__main__":
    main()
