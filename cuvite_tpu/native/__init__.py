"""ctypes bindings for the native host runtime (native/cuvite_native.cpp).

The native library accelerates the host-side data layer — CSR construction,
R-MAT generation, Vite binary I/O — the role the reference fills with its
C++/MPI loader and generator (/root/reference/distgraph.cpp).  Every entry
point has a pure-numpy fallback in the rest of the package, so the library
is an accelerator, never a requirement: ``available()`` gates every use.
The fallbacks are bit-identical, except for the per-phase modularity terms
(``modularity_terms``), which sum in another order than the numpy oracle:
bit-identical for integer-weight graphs, equal to f64 rounding otherwise.

Build: implicitly on first use, from the committed source
``native/cuvite_native.cpp`` (disable with CUVITE_NO_NATIVE=1).  The
built file's name carries a hash of the source, the compiler command and
the machine type, so a stale library, or one built for another host's
CPU, is never loaded: a copied tree rebuilds instead.  A failed build
warns and falls back to numpy.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import warnings

import numpy as np

_LIB = None  # None = not tried; False = unavailable; else CDLL

# Minimum element count for routing through the native library; below this
# the ctypes/copy overhead outweighs the win.  Shared by every dispatch
# site (Graph.from_edges, read/write_vite, phase_modularity).
MIN_NATIVE_EDGES = 1 << 16


# Portable target: no -march=native, so the library runs on any host of
# the machine type it was built for.
CXXFLAGS = ("-O3", "-fopenmp", "-fPIC", "-shared", "-std=c++17", "-Wall")


def _src_path() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "native", "cuvite_native.cpp")


def _compiler() -> tuple:
    return (os.environ.get("CXX", "g++"),) + CXXFLAGS


def _so_path() -> str:
    """The library built from the current source with the current
    compiler command for this machine type."""
    h = hashlib.sha256()
    with open(_src_path(), "rb") as f:
        h.update(f.read())
    h.update(" ".join(_compiler()).encode())
    h.update(platform.machine().encode())
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"libcuvite_native-{h.hexdigest()[:16]}.so")


def _build(so: str) -> str | None:
    """Compile the library to ``so``; returns None or the error text.
    Written to a private temp name, then renamed: concurrent builders
    (pytest-xdist workers) never load a half-written file."""
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        r = subprocess.run([*_compiler(), "-o", tmp, _src_path()],
                           capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        return repr(e)
    if r.returncode != 0:
        return r.stderr[-2000:]
    os.replace(tmp, so)
    return None


def _bind(lib: ctypes.CDLL) -> None:
    i64 = ctypes.c_int64
    u64 = ctypes.c_uint64
    f64 = ctypes.c_double
    p_i64 = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    p_f64 = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
    lib.cv_build_csr.restype = i64
    lib.cv_build_csr.argtypes = [i64, i64, p_i64, p_i64, p_f64,
                                 ctypes.c_int, p_i64, p_i64, p_f64]
    lib.cv_rmat.restype = None
    lib.cv_rmat.argtypes = [ctypes.c_int, i64, u64, f64, f64, f64,
                            p_i64, p_i64]
    lib.cv_vite_header.restype = ctypes.c_int
    lib.cv_vite_header.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                   ctypes.POINTER(i64), ctypes.POINTER(i64)]
    lib.cv_vite_edges.restype = ctypes.c_int
    lib.cv_vite_edges.argtypes = [ctypes.c_char_p, ctypes.c_int, i64, i64,
                                  i64, p_i64, p_f64]
    lib.cv_vite_write.restype = ctypes.c_int
    lib.cv_vite_write.argtypes = [ctypes.c_char_p, ctypes.c_int, i64, i64,
                                  p_i64, p_i64, p_f64]
    lib.cv_balanced_parts.restype = None
    lib.cv_balanced_parts.argtypes = [i64, p_i64, i64, p_i64]
    lib.cv_openmp_threads.restype = ctypes.c_int
    lib.cv_openmp_threads.argtypes = []
    vp = ctypes.c_void_p
    p_u8 = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    p_i32 = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    p_f32 = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    lib.cv_build_csr_unit.restype = i64
    lib.cv_build_csr_unit.argtypes = [i64, i64, p_i32, p_i32, ctypes.c_int,
                                      p_i64, p_i32, p_f32]
    lib.cv_build_csr_w32.restype = i64
    lib.cv_build_csr_w32.argtypes = [i64, i64, vp, vp, p_f64, ctypes.c_int,
                                     ctypes.c_int, p_i64, p_i32, p_f32]
    lib.cv_plan_scan.restype = ctypes.c_int
    lib.cv_plan_scan.argtypes = [i64, i64, i64, vp, vp, vp, ctypes.c_int,
                                 ctypes.c_int, p_f64,
                                 ctypes.POINTER(ctypes.c_int)]
    lib.cv_bucket_fill.restype = ctypes.c_int
    lib.cv_bucket_fill.argtypes = [i64, i64, vp, vp, ctypes.c_int,
                                   ctypes.c_int, p_i64, p_i64, p_u8,
                                   ctypes.c_int, p_i64, p_i64,
                                   ctypes.POINTER(vp), ctypes.POINTER(vp),
                                   ctypes.POINTER(vp), ctypes.c_int, i64,
                                   vp, vp, vp]
    lib.cv_coarsen.restype = i64
    lib.cv_coarsen.argtypes = [i64, i64, p_i64, vp, vp, ctypes.c_int,
                               ctypes.c_int, p_i32, p_i64, p_i32, p_f32,
                               ctypes.c_int]
    lib.cv_weighted_degrees.restype = None
    lib.cv_weighted_degrees.argtypes = [i64, p_i64, vp, ctypes.c_int, p_f64]
    lib.cv_phase_modularity.restype = ctypes.c_int
    lib.cv_phase_modularity.argtypes = [i64, i64, p_i64, vp, vp, vp,
                                        ctypes.c_int, ctypes.c_int,
                                        ctypes.c_int, p_f64, p_f64]


def _load():
    global _LIB
    if _LIB is not None:
        return _LIB or None
    _LIB = False
    if os.environ.get("CUVITE_NO_NATIVE") or not os.path.isfile(
            _src_path()):
        return None
    so = _so_path()
    err = None if os.path.isfile(so) else _build(so)
    if err is None:
        try:
            lib = ctypes.CDLL(so)
            _bind(lib)
            _LIB = lib
        except OSError as e:
            err = repr(e)
    if err is not None:
        warnings.warn(f"native library unavailable, using the numpy "
                      f"fallbacks: {err}", stacklevel=3)
        return None
    return _LIB


def available() -> bool:
    return _load() is not None


def build_csr(num_vertices: int, src: np.ndarray, dst: np.ndarray,
              weights: np.ndarray, symmetrize: bool = True):
    """Edge list -> coalesced CSR, identical to the numpy path in
    Graph.from_edges.  Returns (offsets, tails[f64 ids], weights[f64])."""
    lib = _load()
    assert lib is not None
    src = np.ascontiguousarray(src, dtype=np.int64)
    dst = np.ascontiguousarray(dst, dtype=np.int64)
    w = np.ascontiguousarray(weights, dtype=np.float64)
    cap = 2 * len(src) if symmetrize else len(src)
    cap = max(cap, 1)
    offsets = np.empty(num_vertices + 1, dtype=np.int64)
    tails = np.empty(cap, dtype=np.int64)
    wout = np.empty(cap, dtype=np.float64)
    n = lib.cv_build_csr(num_vertices, len(src), src, dst, w,
                         int(symmetrize), offsets, tails, wout)
    if n < 0:
        raise ValueError("edge endpoint out of range")
    return offsets, tails[:n].copy(), wout[:n].copy()


def build_csr_unit(num_vertices: int, src: np.ndarray, dst: np.ndarray,
                   symmetrize: bool = True):
    """Unit-weight edge list -> coalesced CSR with int32 ids and f32
    duplicate counts as weights — no f64 array exists at any point
    (identical output to build_csr with all-ones weights after the policy
    cast; see cv_build_csr_unit).  Requires num_vertices <= 2^31."""
    lib = _load()
    assert lib is not None
    src = np.ascontiguousarray(src, dtype=np.int32)
    dst = np.ascontiguousarray(dst, dtype=np.int32)
    cap = max(2 * len(src) if symmetrize else len(src), 1)
    offsets = np.empty(num_vertices + 1, dtype=np.int64)
    tails = np.empty(cap, dtype=np.int32)
    wout = np.empty(cap, dtype=np.float32)
    n = lib.cv_build_csr_unit(num_vertices, len(src), src, dst,
                              int(symmetrize), offsets, tails, wout)
    if n < 0:
        raise ValueError("edge endpoint out of range")
    return offsets, tails[:n].copy(), wout[:n].copy()


def build_csr_w(num_vertices: int, src: np.ndarray, dst: np.ndarray,
                w: np.ndarray, symmetrize: bool = True):
    """Weighted edge list -> coalesced CSR with int32 tails and f32
    weights at a ~24 B/slot sort transient (vs the generic path's 32),
    by sorting an int32 original-edge-index payload and gathering f64
    weights only at the linear coalesce (see cv_build_csr_w32 — output
    identical to build_csr + f32 policy cast).  Requires
    num_vertices <= 2^31 and expanded edge count < 2^31."""
    lib = _load()
    assert lib is not None
    src = np.ascontiguousarray(src)
    dst = np.ascontiguousarray(dst)
    if src.dtype != dst.dtype or src.dtype not in (np.int32, np.int64):
        src = np.ascontiguousarray(src, dtype=np.int64)
        dst = np.ascontiguousarray(dst, dtype=np.int64)
    w = np.ascontiguousarray(w, dtype=np.float64)
    cap = max(2 * len(src) if symmetrize else len(src), 1)
    # Validate BEFORE allocating the outputs: at ne near 2^31 the arrays
    # below are ~16 GB, and the native call would only then reject the
    # sizes with one conflated error.
    if num_vertices > (1 << 31):
        raise ValueError(
            f"build_csr_w: num_vertices={num_vertices} exceeds the int32 "
            f"tail id space (2^31); use the generic build_csr path")
    if cap >= (1 << 31):
        raise ValueError(
            f"build_csr_w: expanded edge count {cap} exceeds the int32 "
            f"index payload (2^31); use the generic build_csr path")
    offsets = np.empty(num_vertices + 1, dtype=np.int64)
    tails = np.empty(cap, dtype=np.int32)
    wout = np.empty(cap, dtype=np.float32)
    n = lib.cv_build_csr_w32(num_vertices, len(src), _vp(src), _vp(dst),
                             w, int(src.dtype == np.int64),
                             int(symmetrize), offsets, tails, wout)
    if n < 0:
        raise ValueError("build_csr_w: edge endpoint out of range")
    return offsets, tails[:n].copy(), wout[:n].copy()


def rmat_edges(scale: int, ne: int, seed: int, a: float, b: float, c: float):
    """Counter-based R-MAT edge list (SplitMix64; bit-identical to the numpy
    fallback in cuvite_tpu.io.generate)."""
    lib = _load()
    assert lib is not None
    src = np.empty(ne, dtype=np.int64)
    dst = np.empty(ne, dtype=np.int64)
    lib.cv_rmat(scale, ne, seed, a, b, c, src, dst)
    return src, dst


def vite_header(path: str, bits64: bool):
    lib = _load()
    assert lib is not None
    nv = ctypes.c_int64()
    ne = ctypes.c_int64()
    rc = lib.cv_vite_header(path.encode(), int(bits64),
                            ctypes.byref(nv), ctypes.byref(ne))
    if rc != 0:
        raise ValueError(f"{path}: cannot read Vite header (rc={rc})")
    return int(nv.value), int(ne.value)


def vite_edges(path: str, bits64: bool, nv: int, e0: int, e1: int):
    """Edge records [e0, e1): one sequential read + parallel deinterleave
    into (tails, weights).  Offsets come from the caller (already read and
    validated by read_vite)."""
    lib = _load()
    assert lib is not None
    tails = np.empty(max(e1 - e0, 1), dtype=np.int64)
    weights = np.empty(max(e1 - e0, 1), dtype=np.float64)
    rc = lib.cv_vite_edges(path.encode(), int(bits64), nv, e0, e1, tails,
                           weights)
    if rc != 0:
        raise ValueError(f"{path}: edge read failed (rc={rc})")
    return tails[: e1 - e0], weights[: e1 - e0]


def vite_write(path: str, bits64: bool, offsets: np.ndarray,
               tails: np.ndarray, weights: np.ndarray) -> None:
    lib = _load()
    assert lib is not None
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    tails = np.ascontiguousarray(tails, dtype=np.int64)
    weights = np.ascontiguousarray(weights, dtype=np.float64)
    rc = lib.cv_vite_write(path.encode(), int(bits64), len(offsets) - 1,
                           len(tails), offsets, tails, weights)
    if rc != 0:
        raise ValueError(f"{path}: write failed (rc={rc})")


def balanced_parts(offsets: np.ndarray, nparts: int) -> np.ndarray:
    lib = _load()
    assert lib is not None
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    parts = np.empty(nparts + 1, dtype=np.int64)
    lib.cv_balanced_parts(len(offsets) - 1, offsets, nparts, parts)
    return parts


def _vp(a: np.ndarray):
    return ctypes.c_void_p(a.ctypes.data)


def _mem_available_bytes():
    """Effective available memory: min of Linux MemAvailable and the
    cgroup limit headroom (a container's cgroup cap binds long before
    host-wide MemAvailable does).  None when neither is readable."""
    avail = None
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    avail = int(line.split()[1]) * 1024
                    break
    except (OSError, ValueError, IndexError):
        pass
    # cgroup v2 (memory.max) then v1 (memory.limit_in_bytes): limit minus
    # current usage, ignored when unlimited ("max" / huge sentinel).  In a
    # nested cgroup without a cgroup namespace the process's own limit
    # lives under the subpath from /proc/self/cgroup, so probe every
    # ancestor of that path down to the mount root (ADVICE r4).
    v2_paths = ["/sys/fs/cgroup/memory.max"]
    v1_paths = ["/sys/fs/cgroup/memory/memory.limit_in_bytes"]
    try:
        with open("/proc/self/cgroup") as f:
            for line in f:
                hid, ctrl, path = line.rstrip("\n").split(":", 2)
                path = path.strip("/")
                parts = path.split("/") if path else []
                sub = [
                    "/".join(parts[:i]) for i in range(len(parts), 0, -1)
                ]
                if hid == "0" and not ctrl:  # v2 unified
                    v2_paths[:0] = [
                        f"/sys/fs/cgroup/{s}/memory.max" for s in sub]
                elif "memory" in ctrl.split(","):
                    v1_paths[:0] = [
                        f"/sys/fs/cgroup/memory/{s}/memory.limit_in_bytes"
                        for s in sub]
    except (OSError, ValueError):
        pass
    probes = [(p, p[: -len("memory.max")] + "memory.current")
              for p in v2_paths]
    probes += [(p, p[: -len("memory.limit_in_bytes")]
                + "memory.usage_in_bytes") for p in v1_paths]
    for lim_path, cur_path in probes:
        try:
            with open(lim_path) as f:
                raw = f.read().strip()
            if raw == "max":
                continue
            limit = int(raw)
            if limit >= (1 << 60):  # v1 "unlimited" sentinel
                continue
            with open(cur_path) as f:
                used = int(f.read().strip())
            head = max(limit - used, 0)
            # The binding limit is the MIN over every level that has one.
            avail = head if avail is None else min(avail, head)
        except (OSError, ValueError):
            continue
    return avail


def coarsen_csr(offsets: np.ndarray, tails: np.ndarray, weights: np.ndarray,
                labels: np.ndarray, nc: int):
    """Fused relabel + coalesce of a CSR graph into its community graph
    (see cv_coarsen).  Returns (offsets[i64], tails[i32], weights[f32]);
    requires nc <= 2^31.  Bit-identical to relabel + Graph.from_edges
    (symmetrize=False, f32 weight policy).

    Path choice for nc > 2^22 (below that the dense path always wins):
    the LSD radix's ping-pong transient is 32 B/slot; when that exceeds
    half of MemAvailable, the 12 B/slot counting+dense path is forced so
    benchmark-scale phase-0 coarsens cannot OOM (both paths are
    bit-identical; CUVITE_COARSEN_FORCE=dense|radix overrides)."""
    lib = _load()
    assert lib is not None
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    tails = np.ascontiguousarray(tails)
    assert tails.dtype in (np.int32, np.int64), tails.dtype
    weights = np.ascontiguousarray(weights)
    if weights.dtype not in (np.float32, np.float64):
        weights = weights.astype(np.float32)
    labels = np.ascontiguousarray(labels, dtype=np.int32)
    force_dense = 0
    if nc > (1 << 22):
        knob = os.environ.get("CUVITE_COARSEN_FORCE", "")
        if knob == "dense":
            force_dense = 1
        elif knob != "radix":
            avail = _mem_available_bytes()
            if avail is not None and 32 * len(tails) > avail // 2:
                force_dense = 1
    cap = max(len(tails), 1)
    offsets_out = np.empty(nc + 1, dtype=np.int64)
    tails_out = np.empty(cap, dtype=np.int32)
    wout = np.empty(cap, dtype=np.float32)
    n = lib.cv_coarsen(len(offsets) - 1, nc, offsets, _vp(tails),
                       _vp(weights), int(tails.dtype == np.int64),
                       int(weights.dtype == np.float64), labels,
                       offsets_out, tails_out, wout, force_dense)
    if n < 0:
        raise ValueError("cv_coarsen: label out of range or nc > 2^31")
    return offsets_out, tails_out[:n].copy(), wout[:n].copy()


def weighted_degrees(offsets: np.ndarray, weights: np.ndarray) -> np.ndarray:
    """Per-vertex f64 weighted degree off the CSR (see cv_weighted_degrees);
    bit-identical to np.bincount(sources, weights=w.astype(f64))."""
    lib = _load()
    assert lib is not None
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    weights = np.ascontiguousarray(weights)
    if weights.dtype not in (np.float32, np.float64):
        weights = weights.astype(np.float64)
    out = np.empty(len(offsets) - 1, dtype=np.float64)
    lib.cv_weighted_degrees(len(offsets) - 1, offsets, _vp(weights),
                            int(weights.dtype == np.float64), out)
    return out


def modularity_terms(offsets: np.ndarray, tails: np.ndarray,
                     weights: np.ndarray, comm: np.ndarray, nc: int):
    """One pass over the CSR: ``(e_in, two_m, a_c[nc])`` in f64, the sums
    evaluate/modularity.py::modularity builds from its O(E) temporaries
    (see cv_phase_modularity).  ``comm`` labels each row in [0, nc).
    int32/int64 ids and labels and f32/f64 weights are read in place."""
    lib = _load()
    assert lib is not None
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    nv = len(offsets) - 1
    tails = np.ascontiguousarray(tails)
    if tails.dtype not in (np.int32, np.int64):
        tails = tails.astype(np.int64)
    weights = np.ascontiguousarray(weights)
    if weights.dtype not in (np.float32, np.float64):
        weights = weights.astype(np.float64)
    comm = np.ascontiguousarray(comm)
    if comm.dtype not in (np.int32, np.int64):
        comm = comm.astype(np.int64)
    if len(comm) != nv or len(tails) != len(weights) \
            or int(offsets[-1]) != len(tails):
        raise ValueError(
            f"modularity_terms: {len(comm)} labels, {len(tails)} tails and "
            f"{len(weights)} weights do not fit a CSR of {nv} rows and "
            f"{int(offsets[-1])} slots")
    terms = np.empty(2, dtype=np.float64)
    a_c = np.empty(nc, dtype=np.float64)
    rc = lib.cv_phase_modularity(
        nv, nc, offsets, _vp(tails), _vp(weights), _vp(comm),
        int(tails.dtype == np.int64), int(weights.dtype == np.float64),
        int(comm.dtype == np.int64), terms, a_c)
    if rc != 0:
        raise ValueError(f"modularity_terms: a label lies outside [0, {nc})")
    return terms[0], terms[1], a_c


def plan_scan(src, dst, w, nv: int, base: int):
    """One fused pass over an edge slab: (self_loop[f64 nv], sorted, unit,
    tail_padding_ok).  src/dst must share an int32/int64 dtype; w is
    float32/float64 (see cv_plan_scan)."""
    lib = _load()
    assert lib is not None
    self_loop = np.zeros(nv, dtype=np.float64)
    flags = ctypes.c_int(0)
    rc = lib.cv_plan_scan(
        len(src), nv, base, _vp(src), _vp(dst), _vp(w),
        int(src.dtype == np.int64), int(w.dtype == np.float64),
        self_loop, ctypes.byref(flags))
    if rc != 0:
        raise ValueError(f"cv_plan_scan failed (rc={rc})")
    f = flags.value
    return self_loop, bool(f & 1), bool(f & 2), bool(f & 4)


def bucket_fill(dst, w, nv: int, base: int, row_start, deg, cls,
                widths_kept, nb_pad, verts_list, dmat_list, wmat_list,
                unit: bool, heavy_pad: int, hsrc, hdst, hw) -> None:
    """Stream the CSR-ordered slab into pre-allocated bucket matrices and
    heavy triples (see cv_bucket_fill; caller pre-fills all padding)."""
    lib = _load()
    assert lib is not None
    n = len(widths_kept)
    mk = lambda arrs: (ctypes.c_void_p * max(n, 1))(  # noqa: E731
        *[a.ctypes.data for a in arrs], *([0] * (max(n, 1) - len(arrs))))
    rc = lib.cv_bucket_fill(
        nv, base, _vp(dst), _vp(w),
        int(dst.dtype == np.int64), int(w.dtype == np.float64),
        row_start, deg, cls, n,
        np.ascontiguousarray(widths_kept, dtype=np.int64),
        np.ascontiguousarray(nb_pad, dtype=np.int64),
        mk(verts_list), mk(dmat_list), mk(wmat_list),
        int(unit), heavy_pad, _vp(hsrc), _vp(hdst), _vp(hw))
    if rc != 0:
        raise ValueError(f"cv_bucket_fill failed (rc={rc})")
