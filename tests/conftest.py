"""Test configuration: force an 8-device virtual CPU mesh.

The TPU-native analog of the reference's "multi-node without a cluster"
strategy (oversubscribed MPI ranks on one node, /root/reference/README:48-53):
XLA's host-platform device count gives N fake devices so every collective and
sharding path runs exactly as it would on an N-chip mesh.
"""

import os
import sys

# Must precede any jax backend initialization.  The platform is forced to
# cpu via jax.config below, whatever JAX_PLATFORMS says.
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Stack headroom for XLA's compile worker threads: raise the stack soft
# limit to a large FINITE value before jax loads — glibc sizes new pthread
# stacks from the soft limit (RLIM_INFINITY would fall back to the 8 MiB
# default).  (Historically suspected in the late-run segfault; the real
# cause was the map-count limit above.)
import resource  # noqa: E402

# ROOT CAUSE of the single-process full-suite segfault (round 5,
# tools/segfault_notes.md): XLA:CPU maps each compiled executable's code
# into its own anonymous VMA (plus mprotect splits); a full-suite process
# accumulates ~68k maps and crosses the kernel's vm.max_map_count default
# of 65530, at which point mmap fails inside the executable loader (fresh
# compile or persistent-cache AOT read alike) and it segfaults.  Measured:
# peak 68,415 maps; the suite completes with the limit raised, crashes at
# ~65k without.
# NOTE: this is a HOST-GLOBAL sysctl (no per-process form exists), so the
# raise is strictly OPT-IN — CUVITE_RAISE_SYSCTL=1 — and the prior value
# is restored in pytest_sessionfinish below (graftlint R008 polices this
# pattern).  Without the opt-in, split the suite across processes
# (`pytest -n 3`, where pytest-xdist is installed — it is NOT in this
# image) to keep each process's map count under the kernel default.
_maps_prior = None  # raised from this value iff the opt-in fired
try:
    with open("/proc/sys/vm/max_map_count") as _f:
        _maps_cur = int(_f.read())
except (OSError, ValueError):
    _maps_cur = None
_raise_failed = False  # opt-in was set but the write needed privileges
if os.environ.get("CUVITE_RAISE_SYSCTL"):
    if _maps_cur is not None and _maps_cur < 1 << 20:
        try:
            with open("/proc/sys/vm/max_map_count", "w") as _f:
                _f.write(str(1 << 20))
            _maps_prior = _maps_cur
        except OSError:
            _raise_failed = True


def pytest_configure(config):
    """Warn UP FRONT when no segfault mitigation is active, instead of
    letting a full single-process run segfault at ~95% with no hint (the
    measured peak is ~68,415 maps; 70k adds a little headroom).  Checked
    here rather than at import so an xdist run — controller included —
    is recognized as mitigated; partial runs are fine too, which is why
    this warns rather than fails."""
    if _maps_cur is None:
        if os.environ.get("CUVITE_RAISE_SYSCTL"):
            import warnings

            warnings.warn(
                "CUVITE_RAISE_SYSCTL is set but /proc/sys/vm/"
                "max_map_count is unreadable here, so the raise was "
                "skipped; if a full single-process run segfaults late, "
                "rerun as root, or split it with `pytest -n 3` where "
                "pytest-xdist is installed.", stacklevel=1)
        return
    if _maps_prior is not None or _maps_cur >= 70_000:
        return  # raised via the opt-in, or roomy host
    if os.environ.get("PYTEST_XDIST_WORKER") \
            or getattr(config.option, "numprocesses", None):
        return  # split across processes: per-process map counts stay low
    import warnings

    if _raise_failed:
        # Don't tell the user to set the env var they ALREADY set.
        warnings.warn(
            f"CUVITE_RAISE_SYSCTL was set but raising vm.max_map_count "
            f"(currently {_maps_cur}) failed — the write needs root.  A "
            "full single-process suite run may segfault late in the XLA "
            "executable loader; rerun as root, or split the suite with "
            "`pytest -n 3` where pytest-xdist is installed.",
            stacklevel=1)
        return
    warnings.warn(
        f"vm.max_map_count is {_maps_cur} (< ~70k needed by a full "
        "single-process suite run); a complete run may segfault late in "
        "the XLA executable loader.  Either opt in to the sysctl raise "
        "with CUVITE_RAISE_SYSCTL=1 (root; restored at session finish) "
        "or split the suite with `pytest -n 3` where pytest-xdist is "
        "installed.",
        stacklevel=1)


def pytest_sessionfinish(session, exitstatus):
    """Restore the pre-session vm.max_map_count if the opt-in raised it
    (best-effort: the write needs the same root privilege the raise had)."""
    global _maps_prior
    if _maps_prior is None:
        return
    try:
        # _maps_prior is only ever set under the CUVITE_RAISE_SYSCTL
        # opt-in above; this write UNDOES that raise.
        with open("/proc/sys/vm/max_map_count", "w") as _f:  # graftlint: disable=R008
            _f.write(str(_maps_prior))
    except OSError:
        pass
    _maps_prior = None


_s_soft, _s_hard = resource.getrlimit(resource.RLIMIT_STACK)
_s_want = 512 << 20
# RLIM_INFINITY also needs the finite value: glibc sizes pthread stacks
# from the soft limit only when it is finite (infinity -> 8 MiB default).
if _s_soft == resource.RLIM_INFINITY or _s_soft < _s_want:
    try:
        resource.setrlimit(resource.RLIMIT_STACK, (_s_want, _s_hard))
    except (ValueError, OSError):  # hard limit lower: best effort
        pass

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compile cache for the suite: a full-suite run compiles
# hundreds of programs; the content-addressed disk cache removes most of
# that wall time on warm runs.  (It does NOT remove the map-count growth
# — AOT loads map code pages just like fresh compiles — which is why the
# max_map_count raise above is the actual segfault fix.)
from cuvite_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from cuvite_tpu.core.graph import Graph  # noqa: E402


def karate_edges():
    """Zachary's karate club (34 vertices, 78 edges) — the reference's
    conventional smoke-test input (/root/reference/README:53)."""
    import networkx as nx

    g = nx.karate_club_graph()
    e = np.array(g.edges(), dtype=np.int64)
    return 34, e[:, 0], e[:, 1]


@pytest.fixture(scope="session")
def karate() -> Graph:
    nv, s, d = karate_edges()
    return Graph.from_edges(nv, s, d)


@pytest.fixture(scope="session")
def ring8() -> Graph:
    """8-cycle: trivial known structure."""
    s = np.arange(8)
    d = (s + 1) % 8
    return Graph.from_edges(8, s, d)


@pytest.fixture(scope="session")
def two_cliques() -> Graph:
    """Two K5 cliques joined by a single bridge edge: unambiguous communities."""
    edges = []
    for b in (0, 5):
        for i in range(5):
            for j in range(i + 1, 5):
                edges.append((b + i, b + j))
    edges.append((0, 5))
    e = np.array(edges, dtype=np.int64)
    return Graph.from_edges(10, e[:, 0], e[:, 1])
