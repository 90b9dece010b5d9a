"""graftlint tests: per-rule positive/negative fixtures, suppression
handling, baseline round-trip, and the repo self-lint gate.

The self-lint gate (test_selflint_no_new_high_findings) is the tier-1
enforcement the subsystem exists for: a PR introducing a new
high-severity hazard anywhere in cuvite_tpu/, tools/, or tests/ fails
the suite, with the checked-in baseline (tools/graftlint_baseline.json)
grandfathering whatever was already there when the rule landed.

All fixtures are tiny inline source STRINGS — never repo files — so a
rule's semantics are pinned independently of the codebase's current
state.  ``rel`` paths on fixtures exercise the directory scoping rules
(R003 device-path modules, R007 tools/, R008 tests/).
"""

import json
import os
import subprocess
import sys

import pytest

from cuvite_tpu.analysis import (
    all_rules,
    apply_baseline,
    load_baseline,
    run_paths,
    run_source,
    write_baseline,
)
from cuvite_tpu.analysis.engine import Finding, gate_failures

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "tools", "graftlint_baseline.json")

SCAN_PATHS = ("cuvite_tpu", "tools", "tests")


def rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# Per-rule fixtures: (rule id, triggering source, clean source, rel path).
# The clean variant stays as close to the bad one as the rule allows, so
# each pair pins the rule's discriminating feature, not its surface syntax.

RULE_CASES = [
    (
        "R001",
        """
import jax
import numpy as np

@jax.jit
def step(x):
    return _helper(x)

def _helper(x):
    x.block_until_ready()
    v = float(x.sum())
    return np.asarray(v), x.item()
""",
        """
import jax
import numpy as np

@jax.jit
def step(x):
    return x * 2

def _host_report(x):
    # identical host-sync calls, but NOT reachable from any jitted
    # function in this module
    x.block_until_ready()
    v = float(x.sum())
    return np.asarray(v), x.item()
""",
        "cuvite_tpu/fake_r001.py",
    ),
    (
        "R002",
        """
import functools
import jax

@functools.partial(jax.jit, static_argnums=tuple(range(2)))
def f(a, b, x):
    if x > 0:
        return x
    return -x
""",
        """
import functools
import jax

@functools.partial(jax.jit, static_argnums=(0, 1), static_argnames=("m",))
def f(a, b, x, *, m=4):
    if a > 0:          # static: branch is resolved at trace time
        return x * m
    if x is None:      # structural dispatch, not data-dependent
        return b
    return -x
""",
        "cuvite_tpu/fake_r002.py",
    ),
    (
        "R003",
        """
import jax.numpy as jnp
import numpy as np

def device_ids(n):
    pad = jnp.zeros(n, dtype="int64")
    wide = jnp.full(n, 0, dtype=np.int64)
    cast = jnp.arange(n).astype("int64")
    return pad.astype(jnp.float64), wide, cast
""",
        """
import jax.numpy as jnp
import numpy as np

def device_ids(n):
    # np 64-bit HOST arrays are fine (plan building); only jnp device
    # constructions defeat the 32-bit graph mode
    host = np.zeros(n, dtype=np.int64)
    return jnp.asarray(host, dtype=jnp.int32)
""",
        "cuvite_tpu/louvain/fake_r003.py",
    ),
    (
        "R004",
        """
import jax
from cuvite_tpu.comm.multihost import allgather_varlen, gather_global

def resume(path, arr):
    try:
        state = allgather_varlen(arr)
    except ValueError:
        state = None
    if jax.process_index() == 0:
        return gather_global(arr)
    if _load(path):
        return gather_global(arr)
    return state

def _load(path):
    return None
""",
        """
from cuvite_tpu.comm.multihost import allgather_varlen, gather_global, \\
    is_distributed

def resume(dist_ingest, arr):
    if dist_ingest:          # replicated plain value: uniform by contract
        state = allgather_varlen(arr)
    if is_distributed():     # known-uniform predicate
        return gather_global(arr)
    return state
""",
        "cuvite_tpu/fake_r004.py",
    ),
    (
        "R005",
        """
import numpy as np

def freeze(x, out, acc):
    x.flags.writeable = False
    out[:10] = 0
    np.copyto(out, x)
    acc.fill(0)
    acc += 1 if False else 0
""",
        """
import numpy as np

def freeze(x_ref, o_ref):
    # pallas kernel convention: *_ref params are output Refs
    o_ref[...] = x_ref[...]

def local_only(x):
    out = np.empty_like(x)
    out[:10] = 0          # local allocation: ours to mutate
    out.flags.writeable = False
    np.copyto(out, out)
    return out
""",
        "cuvite_tpu/fake_r005.py",
    ),
    (
        "R006",
        """
import jax.numpy as jnp
from jax.ops import segment_sum

def phase_q(e_c, a_c, seg, n):
    mod = jnp.sum(e_c) - segment_sum(a_c, seg, num_segments=n).sum()
    return mod
""",
        """
import jax.numpy as jnp
from cuvite_tpu.ops.exactsum import ds_tree_sum, ds_to_f64

def phase_q(e_c, a_c):
    mod = ds_tree_sum(e_c - a_c ** 2)
    return mod

def stepped_q(e_c, accum_dtype):
    # dtype-policy-aware: the caller chose the accumulation width
    mod = jnp.sum(e_c.astype(accum_dtype))
    return mod
""",
        "cuvite_tpu/louvain/fake_r006.py",
    ),
    (
        "R007",
        """
import subprocess
import sys

def bench(cmd):
    return subprocess.run([sys.executable] + cmd, capture_output=True)
""",
        """
import subprocess
import sys

def bench(cmd):
    return subprocess.run([sys.executable] + cmd, capture_output=True,
                          timeout=7200)
""",
        "tools/fake_r007.py",
    ),
    (
        "R008",
        """
import os

if not os.environ.get("NO_SYSCTL"):   # opt-OUT: fires by default
    with open("/proc/sys/vm/max_map_count", "w") as f:
        f.write("1048576")
""",
        """
import os

if os.environ.get("RAISE_SYSCTL"):    # opt-IN: off by default
    with open("/proc/sys/vm/max_map_count", "w") as f:
        f.write("1048576")
with open("/proc/sys/vm/max_map_count") as f:   # read-only: fine
    cur = int(f.read())
""",
        "tests/fake_r008.py",
    ),
    (
        "R009",
        """
import urllib.request

def fetch(url, dest):
    with urllib.request.urlopen(url, timeout=60) as resp, \\
            open(dest, "wb") as out:
        out.write(resp.read())
    return dest
""",
        """
import hashlib
import urllib.request

def fetch(url, dest, expected):
    h = hashlib.sha256()
    with urllib.request.urlopen(url, timeout=60) as resp, \\
            open(dest, "wb") as out:
        buf = resp.read()
        h.update(buf)
        out.write(buf)
    _verify_checksum(h.hexdigest(), expected, dest)
    return dest

def _verify_checksum(digest, expected, path):
    if expected is not None and digest != expected:
        raise ValueError(path)
""",
        "cuvite_tpu/workloads/registry.py",
    ),
    (
        "R010",
        """
import jax
import numpy as np

def phase_transition(src_d, labels_d, stats):
    host_slab = jax.device_get(src_d)
    lab = np.asarray(labels_d)
    return host_slab, lab
""",
        """
import numpy as np

def build_plan(plan, comm_pad):
    # host plan arrays: attribute access and non-device-suggestive names
    # are out of scope by design (near-zero false positives)
    src_np = np.asarray(plan.src)
    comm = np.asarray(comm_pad)
    final = np.asarray(labels_d)  # graftlint: disable=R010 — the final label gather
    return src_np, comm, final
""",
        "cuvite_tpu/coarsen/fake_r010.py",
    ),
    (
        "R011",
        """
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def launch(kernel, cT):
    spec = pl.BlockSpec((8, 512), lambda i: (0, i),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(kernel, grid=(4,), in_specs=[spec],
                          out_specs=spec, out_shape=None)(cT)
""",
        """
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128

def launch(kernel, cT, tile):
    D, N = cT.shape
    # dims derived from the ladder-bound shapes; unit dims are layout
    # plumbing, not a tile-size decision
    mat = pl.BlockSpec((D, tile), lambda i: (0, i),
                       memory_space=pltpu.VMEM)
    vec = pl.BlockSpec((1, tile), lambda i: (0, i),
                       memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(kernel, grid=(N // tile,),
                          in_specs=[smem, mat, vec],
                          out_specs=vec, out_shape=None)(cT)
""",
        "cuvite_tpu/kernels/fake_r011.py",
    ),
    (
        "R012",
        """
import time
import jax
import jax.numpy as jnp

@jax.jit
def step(x):
    return x * 2.0

def bench(x):
    t0 = time.perf_counter()
    y = step(jnp.asarray(x))
    dt = time.perf_counter() - t0  # async dispatch: times the launch
    return y, dt
""",
        """
import time
import jax
import jax.numpy as jnp

@jax.jit
def step(x):
    return x * 2.0

def bench(x, opaque_fn):
    t0 = time.perf_counter()
    y = jax.block_until_ready(step(jnp.asarray(x)))
    dt = time.perf_counter() - t0
    # Opaque callables are out of scope: they may sync internally
    # (louvain_phases does), and flagging them would bury the signal.
    t0 = time.perf_counter()
    opaque_fn()
    dt2 = time.perf_counter() - t0
    return y, dt, dt2
""",
        "tools/fake_r012.py",
    ),
    (
        "R013",
        """
import jax
import jax.numpy as jnp

def coalesce(src, dst, w):
    # full-slab sort outside the sanctioned chokepoint: the round-7 tax
    src_s, dst_s, w_s = jax.lax.sort((src, dst, w), num_keys=2)
    order = jnp.argsort(src, stable=True)
    return src_s, dst_s, w_s, order
""",
        """
import jax
import jax.numpy as jnp

from cuvite_tpu.ops import segment as seg

def coalesce(src, dst, w, nv_pad):
    # routed through the sanctioned coalesce chokepoint
    return seg.coalesced_runs(src, dst, w, nv_pad=nv_pad)

def tiny_row_sort(row):
    # a genuinely non-slab sort, justified inline
    return jax.lax.sort((row,), num_keys=1)  # graftlint: disable=R013 — O(D) per-row sort, not a slab

def rebin_degrees(src, real, nv_pad):
    # the ISSUE-19 re-binner idiom: histogram + prefix, NO sort —
    # exactly what this rule's scope exists to keep sort-free
    deg = jax.ops.segment_sum(real.astype(jnp.int32), src,
                              num_segments=nv_pad)
    return deg, jnp.cumsum(deg) - deg
""",
        "cuvite_tpu/coarsen/fake_r013.py",
    ),
    (
        "R014",
        """
import jax

def serve_loop(queue):
    results = []
    while queue:
        job = queue.pop()
        step = jax.jit(lambda s, d, w: s)   # fresh callable: compile per job
        src = jax.device_put(job.src)       # upload per job
        results.append(step(src, job.dst, job.w))
    return results
""",
        """
from cuvite_tpu.louvain.batched import cluster_many

def serve_loop(queue, b_max):
    results = []
    while queue:
        jobs = [queue.pop() for _ in range(min(len(queue), b_max))]
        # one module-scope compiled program, one placement per batch
        br = cluster_many([j.graph for j in jobs])
        results.extend(br.results)
    return results
""",
        "cuvite_tpu/serve/fake_r014.py",
    ),
    (
        "R015",
        """
from cuvite_tpu.louvain.bucketed import BucketPlan

def dispatch(jobs, nv_pad):
    plans = []
    for job in jobs:
        # plan-per-job trap: O(E) gather matrices rebuilt per tenant
        plans.append(BucketPlan.build(job.src, job.dst, job.w,
                                      nv_local=nv_pad, base=0))
    return plans
""",
        """
from cuvite_tpu.core.batch import batch_bucket_plans, batch_slabs
from cuvite_tpu.louvain.bucketed import BucketPlan

def dispatch(jobs, nv_pad):
    # planning at pack time: ONE call covers every row of the batch
    batch = batch_slabs([j.graph for j in jobs])
    return batch_bucket_plans(batch)

def one_off(job, nv_pad):
    # outside any dispatch loop: a single job's plan is fine
    return BucketPlan.build(job.src, job.dst, job.w,
                            nv_local=nv_pad, base=0)

def justified(jobs, nv_pad):
    for job in jobs:
        yield BucketPlan.build(job.src, job.dst, job.w, nv_local=nv_pad, base=0)  # graftlint: disable=R015 — diagnostic path, not dispatch

def coarse_dispatch(batches, nv_pad, geometry):
    from cuvite_tpu.coarsen.rebin import device_rebin_plan

    # the sanctioned in-loop planner (ISSUE 19): coarse phases re-bin
    # ON DEVICE inside the compiled program — not a host plan per job
    for b in batches:
        yield device_rebin_plan(b.src, b.dst, b.w, nv_pad=nv_pad,
                                base=0, geometry=geometry)
""",
        "cuvite_tpu/serve/fake_r015.py",
    ),
    (
        "R016",
        """
import time

def due(queue, linger_s):
    now = time.monotonic()        # untestable-deadline trap
    stamp = time.time()           # ditto (wall time)
    return [j for j in queue if now - j.t_submit >= linger_s], stamp
""",
        """
import time

from cuvite_tpu.serve import clock as serve_clock

def due(queue, linger_s, clock=serve_clock.monotonic):
    # deadlines run on the INJECTED clock; a bare default REFERENCE to
    # time.monotonic is not a call and stays legal
    t0 = time.perf_counter()      # busy timing: allowlisted
    out = [j for j in queue if clock() - j.t_submit >= linger_s]
    busy = time.perf_counter() - t0
    return out, busy

def injected_default(clock=time.monotonic):
    return [clock()]
""",
        "cuvite_tpu/serve/fake_r016.py",
    ),
    (
        "R022",
        """
import threading
from threading import Event, Thread


def start(daemon):
    # direct construction EXITS the sync seam: invisible to every
    # concheck tier-4 schedule
    daemon.lock = threading.Lock()
    daemon.wake = Event()
    t = Thread(target=daemon.run)
    t.start()
    return t
""",
        """
import threading

from cuvite_tpu.serve import sync


def start(daemon):
    # the seam factories: plain threading in production,
    # scheduler-backed twins under concheck
    daemon.lock = sync.Lock()
    daemon.wake = sync.Event()
    t = sync.Thread(target=daemon.run, name="d")
    t.start()
    return t


def annotate(x: threading.RLock) -> None:
    # a bare TYPE reference is not a construction
    pass


def justified():
    return threading.Barrier(2)  # graftlint: disable=R022 — test-harness barrier, never under the scheduler
""",
        "cuvite_tpu/serve/fake_r022.py",
    ),
    (
        "R019",
        """
import threading


class Stats:
    def __init__(self):
        self.lock = threading.RLock()
        self.jobs_done = 0
        self.samples = []

    def record(self, wait):
        with self.lock:
            self.jobs_done += 1
            self.samples.append(wait)

    def racy(self, wait):
        # the PR-11 shape: same fields, no lock — lost updates under the
        # daemon's reader/dispatcher concurrency
        self.jobs_done += 1
        self.samples.append(wait)
""",
        """
import threading


class Stats:
    def __init__(self):
        self.lock = threading.RLock()
        self.jobs_done = 0
        self.samples = []
        self.jobs_done = 0       # ctor re-init: construction, not a race

    def record(self, wait):
        with self.lock:
            self.jobs_done += 1
            self.samples.append(wait)


class SingleThreaded:
    def __init__(self):
        self.count = 0

    def bump(self):
        self.count += 1          # no lock discipline anywhere: unflagged
""",
        "cuvite_tpu/serve/fake_r019.py",
    ),
    (
        "R029",
        """
import jax
import jax.numpy as jnp


def hot_patch(sess, i, weight):
    # direct slab edit outside the apply_delta_slab chokepoint: forks
    # the canonical form the bit-equality tests pin
    sess.w = sess.w.at[i].set(weight)
    sess.src = sess.src.at[i].add(0)
    return sess

_step = jax.jit(lambda s, d, w: (s, d, w), donate_argnums=(2,))
""",
        """
import jax
from cuvite_tpu.stream.delta import apply_delta_slab


def hot_patch(sess, batch, nv_pad, adt):
    # every slab edit routed through the ONE jitted chokepoint
    i_s, i_d, i_w, d_s, d_d = batch.padded(256)
    return apply_delta_slab(sess.src, sess.dst, sess.w,
                            i_s, i_d, i_w, d_s, d_d, sess.ne,
                            nv_pad=nv_pad, accum_dtype=adt)

_step = jax.jit(lambda s, d, w: (s, d, w))


def scratch(mask, idx):
    # a genuinely non-slab update, justified inline
    return mask.at[idx].set(True)  # graftlint: disable=R029 — local scratch mask, never a resident slab
""",
        "cuvite_tpu/stream/fake_r029.py",
    ),
]

RULE_IDS = [c[0] for c in RULE_CASES]


@pytest.mark.parametrize("rule_id,bad,good,rel", RULE_CASES, ids=RULE_IDS)
def test_rule_positive(rule_id, bad, good, rel):
    findings = run_source(bad, rel=rel)
    assert rule_id in rules_of(findings), \
        f"{rule_id} did not fire on its positive fixture: {findings}"


@pytest.mark.parametrize("rule_id,bad,good,rel", RULE_CASES, ids=RULE_IDS)
def test_rule_negative(rule_id, bad, good, rel):
    findings = run_source(good, rel=rel)
    assert rule_id not in rules_of(findings), \
        f"{rule_id} false-positive on its clean fixture: " \
        f"{[f.format() for f in findings if f.rule == rule_id]}"


def test_r014_r015_cover_the_packer_path():
    """ISSUE 20: the per-batch amortization rules extend beyond serve/
    to the PACKER path — pack_*/prepare_*/unpack_* functions in
    louvain/batched.py and core/batch.py hold the same contract (one
    upload, one plan build, zero jit construction per batch, however
    many tenants a merged sub-row batch carries).  Scope stays
    per-function: the phase loops in the same modules legitimately run
    jax calls per iteration."""
    bad = """
import jax

from cuvite_tpu.louvain.bucketed import BucketPlan

def pack_subrow_many(graphs):
    out = []
    for g in graphs:
        buf = jax.device_put(g.src)      # upload per TENANT, not per batch
        plan = BucketPlan.build(g.src, g.dst, g.w, nv_local=4096, base=0)
        out.append((buf, plan))
    return out

def _run_phase_loop(xs):
    # NOT a packer function: in-loop jax here is the phase loop's job
    for x in xs:
        x = jax.device_put(x)
    return xs
"""
    for rel in ("cuvite_tpu/louvain/batched.py",
                "cuvite_tpu/core/batch.py"):
        found = rules_of(run_source(bad, rel=rel))
        assert "R014" in found and "R015" in found, (rel, found)
        # only the packer function's loop fires, not the phase loop's
        lines = [f.line for f in run_source(bad, rel=rel)
                 if f.rule == "R014"]
        assert len(lines) == 1, lines
    # The same source OUTSIDE the packer scope stays silent.
    clean = rules_of(run_source(bad, rel="cuvite_tpu/louvain/fused.py"))
    assert "R014" not in clean and "R015" not in clean


def test_registry_ships_at_least_eight_rules():
    rules = all_rules()
    assert len(rules) >= 8
    assert {r.id for r in rules} >= set(RULE_IDS) | {"R017", "R018"}
    for r in rules:
        assert r.severity in ("high", "medium", "low")
        assert r.title


# ---------------------------------------------------------------------------
# Severity / finding counts on the positive fixtures


def test_positive_fixture_severities_match_registry():
    sev = {r.id: r.severity for r in all_rules()}
    for rule_id, bad, _good, rel in RULE_CASES:
        for f in run_source(bad, rel=rel):
            if f.rule == rule_id:
                assert f.severity == sev[rule_id]


def test_r001_flags_each_sync_call_site():
    bad = RULE_CASES[0][1]
    hits = [f for f in run_source(bad, rel="cuvite_tpu/x.py")
            if f.rule == "R001"]
    # block_until_ready, float, np.asarray, .item
    assert len(hits) == 4


def test_r003_scope_is_device_path_only():
    bad = RULE_CASES[2][1]  # the R003-triggering source
    assert any(f.rule == "R003"
               for f in run_source(bad, rel="cuvite_tpu/ops/x.py"))
    # the SAME source outside louvain/kernels/ops is out of scope
    assert not any(f.rule == "R003"
                   for f in run_source(bad, rel="cuvite_tpu/io/x.py"))


def test_r012_sync_before_dispatch_is_not_evidence():
    # A host-value int() BEFORE the dispatch forces nothing: the window
    # still times only the async launch and must be flagged.
    bad = """
import time
import jax.numpy as jnp

def bench(a, b, nv):
    t0 = time.perf_counter()
    n = int(nv)
    y = jnp.dot(a, b)
    dt = time.perf_counter() - t0
    return y, n, dt
"""
    assert any(f.rule == "R012"
               for f in run_source(bad, rel="tools/x.py"))
    # Same-line wrapping IS evidence: float(jnp.dot(...)) blocks on the
    # result before the window closes.
    good = """
import time
import jax.numpy as jnp

def bench(a, b):
    t0 = time.perf_counter()
    y = float(jnp.dot(a, b))
    dt = time.perf_counter() - t0
    return y, dt
"""
    assert not any(f.rule == "R012"
                   for f in run_source(good, rel="tools/x.py"))
    # A wrapped readback whose argument spans lines still forces the
    # dispatch it encloses (normal 79-char wrapping must not flag).
    wrapped = """
import time
import jax
import jax.numpy as jnp

def bench(a, b):
    t0 = time.perf_counter()
    y = jax.block_until_ready(
        jnp.dot(a, b))
    dt = time.perf_counter() - t0
    return y, dt
"""
    assert not any(f.rule == "R012"
                   for f in run_source(wrapped, rel="tools/x.py"))


R008_GUARD = """
import os

if %s:
    with open("/proc/sys/vm/max_map_count", "w") as f:
        f.write("1048576")
"""


@pytest.mark.parametrize("guard,fires", [
    ("os.environ.get('X')", False),               # opt-in
    ("os.environ.get('X') == '1'", False),        # opt-in, explicit value
    ("os.environ.get('X') is not None", False),   # opt-in
    ("os.environ.get('X', '') != ''", False),     # opt-in
    ("not (os.environ.get('X') is None)", False),  # opt-in, double flip
    ("FLAG and os.environ.get('X')", False),      # conjunction still gates
    ("not os.environ.get('NO_X')", True),         # opt-out
    ("os.environ.get('NO_X') is None", True),     # opt-out, rephrased
    ("os.environ.get('NO_X') == ''", True),       # opt-out, rephrased
    ("os.environ.get('NO_X') != '1'", True),      # opt-out, rephrased
    ("FLAG or os.environ.get('X')", True),        # or-arm bypasses the gate
    ("os.environ.get('X', '1')", True),           # truthy default: not a gate
    ("os.environ.get('X', default='1')", True),   # keyword default, same
])
def test_r008_gate_polarity(guard, fires):
    findings = run_source(R008_GUARD % guard, rel="tests/x.py")
    assert ("R008" in rules_of(findings)) == fires, (guard, findings)


R008_ELSE = """
import os

if %s:
    pass
else:
    with open("/proc/sys/vm/max_map_count", "w") as f:
        f.write("1048576")
"""


@pytest.mark.parametrize("guard,fires", [
    # else of an opt-IN check runs by default when the var is UNSET
    ("os.environ.get('RAISE_X')", True),
    # else of an opt-OUT check runs only when the var IS set: genuine gate
    ("not os.environ.get('NO_X')", False),
    # unprovable polarity must not gate the else branch either
    ("FLAG or os.environ.get('X')", True),
])
def test_r008_else_branch_polarity(guard, fires):
    findings = run_source(R008_ELSE % guard, rel="tests/x.py")
    assert ("R008" in rules_of(findings)) == fires, (guard, findings)


def test_r010_scope_and_name_heuristic():
    bad = RULE_CASES[9][1]
    # In scope under BOTH phase-transition prefixes...
    for rel in ("cuvite_tpu/louvain/x.py", "cuvite_tpu/coarsen/x.py"):
        hits = [f for f in run_source(bad, rel=rel) if f.rule == "R010"]
        # jax.device_get + np.asarray(labels_d): two findings
        assert len(hits) == 2, (rel, hits)
    # ...and silent everywhere else (the same pulls are legitimate on
    # ingest/eval paths where no device-resident slab exists).
    for rel in ("cuvite_tpu/io/x.py", "cuvite_tpu/workloads/x.py",
                "tools/x.py"):
        assert "R010" not in rules_of(run_source(bad, rel=rel)), rel


def test_r010_inline_disable_is_the_allowlist():
    src = """
import jax

def finalize(labels_d):
    return jax.device_get(labels_d)  # graftlint: disable=R010 — final label gather
"""
    assert run_source(src, rel="cuvite_tpu/louvain/x.py") == []


def test_r007_scope_is_tools_only():
    bad = RULE_CASES[6][1]
    assert not any(f.rule == "R007"
                   for f in run_source(bad, rel="cuvite_tpu/x.py"))


def test_r009_network_outside_registry_fires_even_with_checksum():
    # The GOOD registry fixture (checksum-verified download) is still a
    # violation anywhere else: the allowed file is part of the contract.
    good_registry = RULE_CASES[8][2]
    for rel in ("cuvite_tpu/io/vite.py", "tools/grab.py", "tests/x.py"):
        assert "R009" in rules_of(run_source(good_registry, rel=rel)), rel


R009_SUBPROCESS = """
import subprocess

def grab(url, dest):
    subprocess.run(%s, timeout=600, check=True)
"""


@pytest.mark.parametrize("argv,fires", [
    ("['curl', '-o', dest, url]", True),
    ("['wget', '-O', dest, url]", True),
    ("'wget ' + url", False),            # non-constant: cannot prove
    ("['/usr/bin/curl', url]", True),    # path-qualified downloader
    ("['python', '-m', 'x']", False),    # not a downloader
])
def test_r009_subprocess_downloaders(argv, fires):
    findings = run_source(R009_SUBPROCESS % argv,
                          rel="cuvite_tpu/workloads/registry.py")
    assert ("R009" in rules_of(findings)) == fires, (argv, findings)


# ---------------------------------------------------------------------------
# Suppressions

SUPPRESSIBLE = """
import subprocess

def bench(cmd):
    return subprocess.run(cmd)%s
"""


def test_line_suppression():
    dirty = run_source(SUPPRESSIBLE % "", rel="tools/x.py")
    assert rules_of(dirty) == {"R007"}
    clean = run_source(SUPPRESSIBLE % "  # graftlint: disable=R007",
                       rel="tools/x.py")
    assert clean == []


def test_line_suppression_is_rule_specific():
    still = run_source(SUPPRESSIBLE % "  # graftlint: disable=R001",
                       rel="tools/x.py")
    assert rules_of(still) == {"R007"}


def test_line_suppression_all():
    clean = run_source(SUPPRESSIBLE % "  # graftlint: disable=all",
                       rel="tools/x.py")
    assert clean == []


def test_file_suppression_within_pragma_window():
    src = "# graftlint: disable-file=R007\n" + SUPPRESSIBLE % ""
    assert run_source(src, rel="tools/x.py") == []


def test_file_suppression_ignored_past_pragma_window():
    pad = "\n" * 40
    src = SUPPRESSIBLE % "" + pad + "# graftlint: disable-file=R007\n"
    assert rules_of(run_source(src, rel="tools/x.py")) == {"R007"}


# ---------------------------------------------------------------------------
# Baseline round-trip


def _dirty_findings():
    return run_source(SUPPRESSIBLE % "", rel="tools/x.py")


def test_baseline_roundtrip(tmp_path):
    findings = _dirty_findings()
    assert findings
    bl_path = str(tmp_path / "baseline.json")
    write_baseline(bl_path, findings)

    baseline = load_baseline(bl_path)
    new, grandfathered = apply_baseline(_dirty_findings(), baseline)
    assert new == []
    assert len(grandfathered) == len(findings)
    assert gate_failures(new) == []


def test_baseline_survives_line_drift(tmp_path):
    bl_path = str(tmp_path / "baseline.json")
    write_baseline(bl_path, _dirty_findings())
    # Same violation, shifted down by unrelated edits above it: the
    # fingerprint is (path, rule, stripped line), so it stays baselined.
    drifted = run_source("\n# a new comment\n\n" + SUPPRESSIBLE % "",
                         rel="tools/x.py")
    new, old = apply_baseline(drifted, load_baseline(bl_path))
    assert new == [] and len(old) == 1


def test_baseline_does_not_mask_new_findings(tmp_path):
    bl_path = str(tmp_path / "baseline.json")
    write_baseline(bl_path, _dirty_findings())
    two = SUPPRESSIBLE % "" + """
def bench2(cmd):
    return subprocess.run(cmd, check=True)
"""
    new, old = apply_baseline(run_source(two, rel="tools/x.py"),
                              load_baseline(bl_path))
    assert len(old) == 1  # the grandfathered original
    assert len(new) == 1 and new[0].rule == "R007"
    assert gate_failures(new)


def test_e000_is_never_baselineable(tmp_path):
    """A grandfathered parse error must not permanently un-lint a file:
    E000 findings are excluded from write_baseline AND never match a
    (possibly hand-edited) baseline entry."""
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    findings = run_paths([str(bad)])
    assert [f.rule for f in findings] == ["E000"]
    bl_path = str(tmp_path / "baseline.json")
    write_baseline(bl_path, findings)
    assert load_baseline(bl_path) == {}  # not written...
    forged = {findings[0].fingerprint(): 1}
    new, old = apply_baseline(findings, forged)  # ...and never matched
    assert old == [] and new == findings


def test_pragma_inside_string_literal_is_ignored():
    """A docstring QUOTING the suppression syntax must not disable the
    gate for the file that quotes it."""
    src = '''"""Docs.

The suppression syntax is:
# graftlint: disable-file=all
"""
import subprocess

def bench(cmd):
    return subprocess.run(cmd)
'''
    assert rules_of(run_source(src, rel="tools/x.py")) == {"R007"}
    # ...while a REAL comment pragma still works
    real = "# graftlint: disable-file=R007\n" + src
    assert run_source(real, rel="tools/x.py") == []


def test_baseline_missing_file_is_empty():
    assert load_baseline("/nonexistent/baseline.json") == {}


def test_baseline_counts_duplicates(tmp_path):
    f = Finding(rule="R007", severity="high", path="tools/x.py", line=4,
                message="m", snippet="subprocess.run(cmd)")
    g = Finding(rule="R007", severity="high", path="tools/x.py", line=9,
                message="m", snippet="subprocess.run(cmd)")
    bl_path = str(tmp_path / "baseline.json")
    write_baseline(bl_path, [f])  # ONE slot for this fingerprint
    new, old = apply_baseline([f, g], load_baseline(bl_path))
    assert len(old) == 1 and len(new) == 1


# ---------------------------------------------------------------------------
# Engine behaviour


def test_syntax_error_yields_gateable_finding(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    findings = run_paths([str(p)])
    assert len(findings) == 1
    assert findings[0].rule == "E000" and findings[0].severity == "high"
    assert gate_failures(findings)


def test_unreadable_sources_fail_closed(tmp_path):
    """Non-UTF8 bytes and null bytes must become E000 findings, not an
    uncaught exception that discards every other file's findings."""
    latin = tmp_path / "latin.py"
    latin.write_bytes(b"# caf\xe9\n")
    nul = tmp_path / "nul.py"
    nul.write_bytes(b"x = 1\x00\n")
    findings = run_paths([str(latin), str(nul)])
    assert [f.rule for f in findings] == ["E000", "E000"]
    assert gate_failures(findings)


def test_barren_path_fails_closed(tmp_path):
    """A typo'd / renamed input directory must NOT report a green gate."""
    empty = tmp_path / "empty"
    empty.mkdir()
    for bad in ("/nonexistent/tree", str(empty)):
        findings = run_paths([bad])
        assert [f.rule for f in findings] == ["E000"]
        assert gate_failures(findings)


def test_run_paths_walks_directories(tmp_path):
    sub = tmp_path / "tools"
    sub.mkdir()
    (sub / "a.py").write_text(SUPPRESSIBLE % "")
    (sub / "skip.txt").write_text("subprocess.run(x)")
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        findings = run_paths(["tools"])
    finally:
        os.chdir(cwd)
    assert rules_of(findings) == {"R007"}
    assert findings[0].path == "tools/a.py"


# ---------------------------------------------------------------------------
# The gate itself


def test_selflint_no_new_high_findings(monkeypatch):
    """THE tier-1 gate: zero non-baselined high-severity findings across
    the repo's source, tools, and tests — ALL tiers (per-file rules,
    the cross-module R017/R018 pass, the serve/ lockset R019).  Runs
    through the incremental cache (the same one tools/lint.sh warms; a
    hit is pinned bit-identical to cold by
    test_cache_hit_bit_identical)."""
    import warnings as _warnings

    from cuvite_tpu.analysis.engine import stale_baseline_entries

    monkeypatch.chdir(REPO)
    findings = run_paths(SCAN_PATHS, cache=os.path.join(
        REPO, "tools", ".graftlint_cache.json"))
    baseline = load_baseline(BASELINE)
    new, _ = apply_baseline(findings, baseline)
    failures = gate_failures(new, "high")
    assert not failures, \
        "new high-severity graftlint findings (fix, suppress with a " \
        "justified '# graftlint: disable=R###', or re-baseline " \
        "deliberately via tools/lint.sh --write-baseline):\n" + \
        "\n".join(f.format() for f in failures)
    # Baseline hygiene rides along as a WARNING, not a failure: a dead
    # entry silently admits one future regression at its fingerprint.
    stale = stale_baseline_entries(findings, baseline)
    if stale:
        _warnings.warn(
            "graftlint baseline has stale entries (run tools/lint.sh "
            f"--prune-baseline): {stale}")


def test_gate_is_cwd_independent(tmp_path, monkeypatch):
    """Paths are anchored to the REPO ROOT, not the CWD: linting the
    repo by absolute path from elsewhere must keep the scoped rules on
    and the baseline matching."""
    from cuvite_tpu.analysis.engine import _relpath

    monkeypatch.chdir(tmp_path)
    assert _relpath(os.path.join(REPO, "tools", "lint.sh")) \
        == "tools/lint.sh"
    findings = run_paths([os.path.join(REPO, p) for p in SCAN_PATHS])
    assert all(not f.path.startswith(("/", "..")) for f in findings)
    new, _ = apply_baseline(findings, load_baseline(BASELINE))
    assert not gate_failures(new, "high")
    # ...while trees OUTSIDE the repo resolve against the scan-root
    # anchor, so scoped rules work on them from ANY CWD
    sub = tmp_path / "deep" / "nested" / "tools"
    sub.mkdir(parents=True)
    (sub / "a.py").write_text(SUPPRESSIBLE % "")
    assert rules_of(run_paths(["deep/nested/tools"])) == {"R007"}
    monkeypatch.chdir("/")  # ancestor CWD: anchor must still win
    assert rules_of(run_paths([str(sub)])) == {"R007"}
    # a single FILE under a scoped dir keeps the scoping component too
    assert rules_of(run_paths([str(sub / "a.py")])) == {"R007"}


def test_write_baseline_cli_reports_e000(tmp_path, capsys):
    """--write-baseline must not claim it captured unparsable files, and
    must exit nonzero so a rebaseline doesn't green-wash an E000."""
    from cuvite_tpu.analysis.__main__ import main

    tree = tmp_path / "tools"
    tree.mkdir()
    (tree / "bad.py").write_text(SUPPRESSIBLE % "")
    (tree / "broken.py").write_text("def f(:\n")
    bl = str(tmp_path / "bl.json")
    rc = main([str(tree), "--baseline", bl, "--write-baseline"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "wrote 1 finding(s)" in out and "NOT baselined" in out
    assert len(load_baseline(bl)) == 1


@pytest.mark.slow
def test_cli_gate_matches_library(monkeypatch, capsys):
    """Tier-2 (slow): this is a second ~13 s full-repo gate scan whose
    tier-1 coverage lives in test_gate_is_cwd_independent (same
    run_paths + baseline + gate over SCAN_PATHS) and, for the real CLI
    surface, test_cli_subprocess_entrypoint."""
    from cuvite_tpu.analysis.__main__ import main

    monkeypatch.chdir(REPO)
    rc = main(list(SCAN_PATHS) + ["--baseline", BASELINE,
                                  "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["gate"]["failures"] == 0


def test_cli_list_rules(capsys):
    from cuvite_tpu.analysis.__main__ import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in RULE_IDS:
        assert rid in out


@pytest.mark.slow
def test_cli_subprocess_entrypoint():
    """`python -m cuvite_tpu.analysis` works as a real child process
    (what tools/lint.sh and CI invoke)."""
    out = subprocess.run(
        [sys.executable, "-m", "cuvite_tpu.analysis", *SCAN_PATHS,
         "--baseline", BASELINE],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr


# ---------------------------------------------------------------------------
# Tier 2: cross-module jit-reachability (R017/R018).  Fixtures are
# multi-file {rel: source} projects linted through run_project_sources —
# the same path run_paths takes for a tree on disk.

from cuvite_tpu.analysis import run_project_sources  # noqa: E402

R017_DEEP = {
    # jit root -> mid helper (module 2) -> device_get (module 3): the
    # exact false negative ANALYSIS.md used to document as out of scope.
    "cuvite_tpu/louvain/fake_root.py": """
import jax

from cuvite_tpu.fake_mid import mid_helper

@jax.jit
def step(x):
    return mid_helper(x)
""",
    "cuvite_tpu/fake_mid.py": """
from cuvite_tpu.fake_deep import deep_pull

def mid_helper(x):
    return deep_pull(x) + 1
""",
    "cuvite_tpu/fake_deep.py": """
import jax

def deep_pull(x):
    return jax.device_get(x)
""",
}


def test_r017_transitive_device_get_two_modules_deep():
    findings = run_project_sources(R017_DEEP)
    hits = [f for f in findings if f.rule == "R017"]
    assert len(hits) == 1, findings
    assert hits[0].path == "cuvite_tpu/fake_deep.py"
    assert "fake_root.py::step" in hits[0].message  # the reach chain
    assert hits[0].severity == "high"


def test_r017_negative_without_entry_point():
    # Identical modules, no @jax.jit: plain host code, nothing fires.
    clean = dict(R017_DEEP)
    clean["cuvite_tpu/louvain/fake_root.py"] = \
        clean["cuvite_tpu/louvain/fake_root.py"].replace("@jax.jit\n", "")
    assert not any(f.rule == "R017"
                   for f in run_project_sources(clean))


def test_r017_defers_to_r001_in_module():
    # A same-module reachable sync is R001's finding; R017 must not
    # double-report it.
    src = {
        "cuvite_tpu/fake_one.py": """
import jax

@jax.jit
def step(x):
    return helper(x)

def helper(x):
    return jax.device_get(x)
""",
    }
    rules = {f.rule for f in run_project_sources(src)}
    assert "R001" in rules and "R017" not in rules


def test_r017_factory_partial_shard_map_idiom():
    """The louvain/batched.py shape: the traced body reaches jit only
    through a functools.partial assigned to a local, wrapped in
    shard_map — the per-file engine misses it, tier 2 must not."""
    src = {
        "cuvite_tpu/fake_factory.py": """
import functools
import jax

from cuvite_tpu.fake_body import phase_body

def get_phase(mesh, nv_pad):
    body = functools.partial(phase_body, nv_pad=nv_pad)
    return jax.jit(shard_map(body, mesh=mesh))

def shard_map(f, mesh):
    return f
""",
        "cuvite_tpu/fake_body.py": """
import numpy as np

def phase_body(x, *, nv_pad):
    return np.asarray(x)
""",
    }
    hits = [f for f in run_project_sources(src) if f.rule == "R017"]
    assert len(hits) == 1 and hits[0].path == "cuvite_tpu/fake_body.py"


def test_r017_inline_suppression():
    src = dict(R017_DEEP)
    src["cuvite_tpu/fake_deep.py"] = """
import jax

def deep_pull(x):
    return jax.device_get(x)  # graftlint: disable=R017 — final gather
"""
    assert not any(f.rule == "R017" for f in run_project_sources(src))


R018_PROJECT = {
    "cuvite_tpu/coarsen/fake_phase.py": """
from cuvite_tpu.utils.fake_pull import pull_stats

def phase_transition(slab_d):
    return pull_stats(slab_d)
""",
    "cuvite_tpu/utils/fake_pull.py": """
import jax

def pull_stats(slab_d):
    return jax.device_get(slab_d)
""",
}


def test_r018_pull_in_helper_reached_from_coarsen():
    findings = run_project_sources(R018_PROJECT)
    hits = [f for f in findings if f.rule == "R018"]
    assert len(hits) == 1, findings
    assert hits[0].path == "cuvite_tpu/utils/fake_pull.py"
    assert "fake_phase.py::phase_transition" in hits[0].message


def test_r018_negative_unreached_helper():
    # The same helper reached only from tools/: no phase-transition
    # caller, no finding (and R010 stays silent outside its scope).
    src = {
        "tools/fake_bench.py": R018_PROJECT[
            "cuvite_tpu/coarsen/fake_phase.py"],
        "cuvite_tpu/utils/fake_pull.py": R018_PROJECT[
            "cuvite_tpu/utils/fake_pull.py"],
    }
    assert not any(f.rule in ("R018", "R010")
                   for f in run_project_sources(src))


def test_r018_in_scope_modules_stay_r010():
    # A pull INSIDE louvain//coarsen/ is R010's (baselined, medium)
    # finding; R018 covers only the helpers those modules reach.
    src = {"cuvite_tpu/coarsen/fake_self.py": """
import jax

def phase_transition(slab_d):
    return jax.device_get(slab_d)
"""}
    rules = {f.rule for f in run_project_sources(src)}
    assert "R010" in rules and "R018" not in rules


# ---------------------------------------------------------------------------
# Tier 2b: lockset checker details beyond the RULE_CASES pair.


R019_SEEDED_PR11 = """
import threading


class ServeStats:
    def __init__(self):
        self.lock = threading.RLock()
        self.jobs_done = 0
        self.wait_samples = []


class Dispatcher:
    def __init__(self, stats):
        self.stats = stats

    def locked_path(self, wait):
        with self.stats.lock:
            self.stats.jobs_done += 1
            self.stats.wait_samples.append(wait)

    def drain_recheck(self, wait):
        # the PR-11 drain-recheck bug shape: the happy path takes the
        # lock, the drain path forgot it
        self.stats.jobs_done += 1
        self.stats.wait_samples.append(wait)
"""


def test_r019_seeded_pr11_unguarded_mutation():
    hits = [f for f in run_source(R019_SEEDED_PR11,
                                  rel="cuvite_tpu/serve/fake_seed.py")
            if f.rule == "R019"]
    assert len(hits) == 2, hits          # jobs_done += and .append
    assert all("self.stats.lock" in f.message for f in hits)
    assert all(f.severity == "high" for f in hits)


def test_r019_scope_is_serve_only():
    assert not any(
        f.rule == "R019"
        for f in run_source(R019_SEEDED_PR11,
                            rel="cuvite_tpu/louvain/fake_seed.py"))


def test_r019_guarded_by_annotation():
    """The explicit annotation establishes the discipline when NO
    in-class mutation ever takes the lock (inference has nothing to
    infer from)."""
    src = """
import threading


class Stats:
    lock: object = None
    jobs_done: int = 0  # graftlint: guarded-by=self.lock

    def racy(self):
        self.jobs_done += 1
"""
    hits = [f for f in run_source(src, rel="cuvite_tpu/serve/fake.py")
            if f.rule == "R019"]
    assert len(hits) == 1 and "self.lock" in hits[0].message
    # ...and holding the annotated lock satisfies it.
    good = src.replace("        self.jobs_done += 1",
                       "        with self.lock:\n"
                       "            self.jobs_done += 1")
    assert not any(f.rule == "R019"
                   for f in run_source(good,
                                       rel="cuvite_tpu/serve/fake.py"))


def test_r019_nested_class_does_not_cross_pollute():
    """An inner class's mutations must not inherit (or feed) the outer
    class's inferred guards."""
    src = """
import threading


class Outer:
    def __init__(self):
        self.lock = threading.RLock()
        self.count = 0

    def locked(self):
        with self.lock:
            self.count += 1

    class Inner:
        def bump(self):
            self.count += 1   # Inner has no lock discipline of its own
"""
    assert not any(f.rule == "R019"
                   for f in run_source(src,
                                       rel="cuvite_tpu/serve/fake.py"))


def test_r019_inline_suppression():
    suffix = "  # graftlint: disable=R019 — single-threaded teardown"
    lines = R019_SEEDED_PR11.splitlines()
    # Suppress the two drain_recheck mutations (the last two statements).
    drain_at = lines.index("    def drain_recheck(self, wait):")
    out = [ln + suffix
           if i > drain_at and ln.strip().startswith("self.stats.")
           else ln
           for i, ln in enumerate(lines)]
    hits = [f for f in run_source("\n".join(out),
                                  rel="cuvite_tpu/serve/fake.py")
            if f.rule == "R019"]
    assert hits == [], hits


def test_r019_real_serve_package_self_lints_clean(monkeypatch):
    """The acceptance pin: the REAL serve/ package carries no unguarded
    mutation of an inferred/annotated guarded field."""
    monkeypatch.chdir(REPO)
    findings = run_paths(["cuvite_tpu/serve"], project=False)
    assert not [f for f in findings if f.rule == "R019"], findings


# ---------------------------------------------------------------------------
# Tier 5 (static): SPMD mesh/collective rules R023-R025.  Fixtures are
# multi-file projects through run_project_sources, like tier 2's.

MESH5_MESH = """
import numpy as np
from jax.sharding import Mesh

VERTEX_AXIS = "v"
BATCH_AXIS = "b"

def make(devs):
    return Mesh(np.array(devs), (VERTEX_AXIS,))

def make_batch(devs):
    return Mesh(np.array(devs), (BATCH_AXIS,))
"""

MESH5_STEP = """
import jax
from cuvite_tpu.fake_mesh5 import VERTEX_AXIS
from cuvite_tpu.fake_helper5 import tail_sum

def make_step(mesh):
    def step(x, flag):
        return tail_sum(x, VERTEX_AXIS, flag)
    return jax.jit(shard_map(step, mesh=mesh, in_specs=P(VERTEX_AXIS),
                             out_specs=P(VERTEX_AXIS)))
"""


def _mesh5_project(helper_src):
    return {
        "cuvite_tpu/fake_mesh5.py": MESH5_MESH,
        "cuvite_tpu/fake_step5.py": MESH5_STEP,
        "cuvite_tpu/fake_helper5.py": helper_src,
    }


MESH5_HELPER_DRIFT = """
import jax

def tail_sum(x, axis_name, flag):
    return jax.lax.psum(x, "ici")
"""

MESH5_HELPER_WRONG_AXIS = """
import jax

def tail_sum(x, axis_name, flag):
    return jax.lax.psum(x, "b")
"""

MESH5_HELPER_DIVERGENT = """
import jax

def tail_sum(x, axis_name, flag):
    if flag.any():
        return jax.lax.psum(x, axis_name)
    return x
"""

MESH5_HELPER_CLEAN = """
import jax

def tail_sum(x, axis_name, flag):
    return jax.lax.psum(x, axis_name)
"""


def test_r023_unknown_axis_cross_module():
    findings = run_project_sources(_mesh5_project(MESH5_HELPER_DRIFT))
    hits = [f for f in findings if f.rule == "R023"]
    assert len(hits) == 1, findings
    assert hits[0].path == "cuvite_tpu/fake_helper5.py"
    assert "'ici'" in hits[0].message
    assert "fake_step5.py::step" in hits[0].message  # the reach chain


def test_r023_per_wrap_axis_mismatch():
    # 'b' IS a constructed mesh axis, but every wrap reaching the
    # helper maps only 'v': the two-level-split bug class.
    findings = run_project_sources(
        _mesh5_project(MESH5_HELPER_WRONG_AXIS))
    hits = [f for f in findings if f.rule == "R023"]
    assert len(hits) == 1, findings
    assert "maps only axes ['v']" in hits[0].message


def test_r023_multi_wrap_union_admits_both_axes():
    """A helper reached from BOTH the vertex-sharded and the
    batch-sharded wrap admits the union of their axes: psum over
    either axis is legal, conviction requires disjointness from EVERY
    reaching wrap (the fixpoint over all call edges, not the BFS
    tree)."""
    src = _mesh5_project(MESH5_HELPER_WRONG_AXIS)  # psum over 'b'
    src["cuvite_tpu/fake_bstep5.py"] = """
import jax
from cuvite_tpu.fake_mesh5 import BATCH_AXIS
from cuvite_tpu.fake_helper5 import tail_sum

def make_bstep(mesh):
    def bstep(x, flag):
        return tail_sum(x, BATCH_AXIS, flag)
    return jax.jit(shard_map(bstep, mesh=mesh, in_specs=P(BATCH_AXIS),
                             out_specs=P(BATCH_AXIS)))
"""
    assert not any(f.rule == "R023"
                   for f in run_project_sources(src))


def test_r023_param_axis_resolves_clean():
    # axis_name chases its call-site binding (VERTEX_AXIS -> "v")
    # through the wrap: no finding.
    findings = run_project_sources(_mesh5_project(MESH5_HELPER_CLEAN))
    assert not any(f.rule in ("R023", "R024", "R025") for f in findings)


def test_r023_no_wrap_no_finding():
    src = _mesh5_project(MESH5_HELPER_DRIFT)
    src["cuvite_tpu/fake_step5.py"] = MESH5_STEP.replace(
        "shard_map(step, mesh=mesh, in_specs=P(VERTEX_AXIS),\n"
        "                             out_specs=P(VERTEX_AXIS))", "step")
    assert not any(f.rule == "R023"
                   for f in run_project_sources(src))


def test_r023_axis_index_first_positional_axis():
    # axis_index takes the axis name as its FIRST argument (review
    # regression: the axis-arg reader only looked at position 1).
    findings = run_project_sources(_mesh5_project("""
import jax

def tail_sum(x, axis_name, flag):
    me = jax.lax.axis_index("ici")
    return x + me
"""))
    hits = [f for f in findings if f.rule == "R023"]
    assert len(hits) == 1 and "'ici'" in hits[0].message


def test_r023_inline_suppression():
    src = _mesh5_project(MESH5_HELPER_DRIFT.replace(
        'jax.lax.psum(x, "ici")',
        'jax.lax.psum(x, "ici")  # graftlint: disable=R023 — staged axis'))
    assert not any(f.rule == "R023" for f in run_project_sources(src))


# Hybrid 2-D ('dcn','ici') mesh project: the two-level exchange shape.
MESH5_HYBRID_MESH = """
import numpy as np
from jax.sharding import Mesh

DCN_AXIS = "dcn"
ICI_AXIS = "ici"

def make_hybrid(devs, n_dcn, n_ici):
    return Mesh(np.array(devs).reshape(n_dcn, n_ici),
                (DCN_AXIS, ICI_AXIS))
"""

MESH5_HYBRID_STEP = """
import jax
from cuvite_tpu.fake_hmesh5 import DCN_AXIS, ICI_AXIS
from cuvite_tpu.fake_htable5 import group_tables

def make_step(mesh):
    def step(comm, vdeg):
        return group_tables(comm, vdeg, DCN_AXIS, ICI_AXIS)
    return jax.jit(shard_map(step, mesh=mesh,
                             in_specs=P((DCN_AXIS, ICI_AXIS)),
                             out_specs=P((DCN_AXIS, ICI_AXIS))))
"""

MESH5_HYBRID_TABLE_CLEAN = """
import jax

def group_tables(comm, vdeg, dcn_axis, ici_axis):
    comm_g = jax.lax.all_gather(comm, ici_axis, tiled=True)
    vdeg_g = jax.lax.all_gather(vdeg, ici_axis, tiled=True)
    return comm_g[: comm.shape[0]] + vdeg_g[: vdeg.shape[0]]
"""


def _mesh5_hybrid_project(table_src):
    return {
        "cuvite_tpu/fake_hmesh5.py": MESH5_HYBRID_MESH,
        "cuvite_tpu/fake_hstep5.py": MESH5_HYBRID_STEP,
        "cuvite_tpu/fake_htable5.py": table_src,
    }


def test_r023_hybrid_ici_gather_clean():
    # The narrowed two-level table gather (ICI axis via the wrap's
    # binding) is legal on the 2-D hybrid mesh: no finding.
    findings = run_project_sources(
        _mesh5_hybrid_project(MESH5_HYBRID_TABLE_CLEAN))
    assert not any(f.rule in ("R023", "R024") for f in findings), findings


def test_r023_hybrid_table_rewidened_to_flat_axis_convicted():
    """ISSUE 18 sabotage, static half: re-widening one group table's
    gather from the ICI submesh back to the retired flat global axis
    ('v' — which no mesh in the hybrid project constructs) is exactly
    an axis-name edit, and R023 convicts it cross-module."""
    sab = MESH5_HYBRID_TABLE_CLEAN.replace(
        'jax.lax.all_gather(comm, ici_axis, tiled=True)',
        'jax.lax.all_gather(comm, "v", tiled=True)')
    findings = run_project_sources(_mesh5_hybrid_project(sab))
    hits = [f for f in findings if f.rule == "R023"]
    assert len(hits) == 1, findings
    assert hits[0].path == "cuvite_tpu/fake_htable5.py"
    assert "'v'" in hits[0].message
    assert "fake_hstep5.py::step" in hits[0].message


def test_r024_conditional_collective_cross_module():
    findings = run_project_sources(
        _mesh5_project(MESH5_HELPER_DIVERGENT))
    hits = [f for f in findings if f.rule == "R024"]
    assert len(hits) == 1, findings
    assert hits[0].path == "cuvite_tpu/fake_helper5.py"
    assert "flag.any" in hits[0].message
    assert "fake_step5.py::step" in hits[0].message
    # Unconditional collective in the same shape: clean (pinned above
    # by test_r023_param_axis_resolves_clean).


def test_r024_requires_shard_map_reach():
    # The same divergent helper with NO shard_map anywhere: host-side
    # code, R024 stays silent (R004 covers host collective wrappers).
    src = {"cuvite_tpu/fake_solo5.py": MESH5_HELPER_DIVERGENT}
    assert not any(f.rule == "R024" for f in run_project_sources(src))


def test_r024_leaves_host_wrappers_to_r004():
    src = _mesh5_project("""
from cuvite_tpu.comm.multihost import gather_global

def tail_sum(x, axis_name, flag):
    if flag.any():
        return gather_global(x)
    return x
""")
    rules = {f.rule for f in run_project_sources(src)}
    assert "R004" in rules and "R024" not in rules


R025_TABLE = """
import jax
import jax.numpy as jnp

def make_step(mesh, nv_total):
    def step(vdeg, comm):
        table = jnp.zeros((nv_total,), dtype=vdeg.dtype)%s
        return jax.lax.psum(table, "v")
    return jax.jit(shard_map(step, mesh=mesh, in_specs=P("v"),
                             out_specs=P("v")))
"""


def test_r025_unannotated_nv_total_table():
    src = {"cuvite_tpu/fake_r025.py": R025_TABLE % "",
           "cuvite_tpu/fake_mesh5.py": MESH5_MESH}
    hits = [f for f in run_project_sources(src) if f.rule == "R025"]
    assert len(hits) == 1, hits
    assert "nv_total" in hits[0].message
    assert "replicated-ok" in hits[0].message


def test_r025_replicated_ok_annotation_closes_the_finding():
    src = {"cuvite_tpu/fake_r025.py": R025_TABLE
           % "  # graftlint: replicated-ok=frozen community table",
           "cuvite_tpu/fake_mesh5.py": MESH5_MESH}
    assert not any(f.rule == "R025" for f in run_project_sources(src))
    # ... and the annotated site lands in the closed inventory.
    from cuvite_tpu.analysis.callgraph import summarize
    from cuvite_tpu.analysis.engine import SourceFile
    from cuvite_tpu.analysis.meshspec import replicated_inventory

    rel = "cuvite_tpu/fake_r025.py"
    inv = replicated_inventory(
        [summarize(SourceFile(src[rel], path=rel, rel=rel))])
    assert len(inv) == 1
    assert inv[0]["reason"] == "frozen community table"


def test_r025_positional_and_broadcast_spellings_convict():
    """Review regressions: ``num_segments`` spelled POSITIONALLY
    (segment_sum(data, ids, nv_total)) and ``broadcast_to`` (whose
    shape is the SECOND positional) materialize the same O(nv_total)
    table and must convict like the keyword/zeros spellings."""
    src = {"cuvite_tpu/fake_r025pos.py": """
import jax
import jax.numpy as jnp

def make_step(mesh, nv_total):
    def step(vdeg, comm):
        deg = seg.segment_sum(vdeg, comm, nv_total)
        rep = jnp.broadcast_to(vdeg[:1], (nv_total,))
        return jax.lax.psum(deg + rep, "v")
    return jax.jit(shard_map(step, mesh=mesh, in_specs=P("v"),
                             out_specs=P("v")))
""",
           "cuvite_tpu/fake_mesh5.py": MESH5_MESH}
    hits = [f for f in run_project_sources(src) if f.rule == "R025"]
    assert len(hits) == 2, hits


def test_r025_unreached_table_is_clean():
    # nv_total-sized table in plain host code (no shard_map reach):
    # one copy on one device is not replication.
    src = {"cuvite_tpu/fake_host25.py": """
import jax.numpy as jnp

def table_of(nv_total):
    return jnp.zeros((nv_total,), dtype="int32")
"""}
    assert not any(f.rule == "R025" for f in run_project_sources(src))


def test_tier5_rules_ride_the_cache_warm_equals_cold(tmp_path):
    """R023 findings come from PROJECT-linked mesh facts riding the
    tier-2 summaries: a warm (all-hits) run must reproduce them bit-
    identically from the cache without reparsing."""
    tree = tmp_path / "cuvite_tpu"
    tree.mkdir()
    (tree / "fake_mesh5.py").write_text(MESH5_MESH)
    (tree / "fake_step5.py").write_text(MESH5_STEP)
    (tree / "fake_helper5.py").write_text(MESH5_HELPER_DRIFT)
    cache = str(tmp_path / "cache.json")
    cold = run_paths([str(tree)], cache=cache)
    warm = run_paths([str(tree)], cache=cache)
    assert cold == warm
    assert any(f.rule == "R023" for f in warm)


def test_tier5_sarif_roundtrip():
    from cuvite_tpu.analysis.__main__ import to_sarif

    findings = run_project_sources(_mesh5_project(MESH5_HELPER_DRIFT))
    doc = to_sarif([f for f in findings if f.rule == "R023"])
    run = doc["runs"][0]
    meta_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"R023", "R024", "R025"} <= meta_ids
    assert [r["ruleId"] for r in run["results"]] == ["R023"]
    assert run["results"][0]["level"] == "error"
    assert run["results"][0]["partialFingerprints"]


# ---------------------------------------------------------------------------
# Incremental cache: hit == cold, bit for bit; edits invalidate.


def _mini_tree(tmp_path):
    tree = tmp_path / "tools"
    tree.mkdir()
    (tree / "a.py").write_text(SUPPRESSIBLE % "")
    (tree / "b.py").write_text("def ok():\n    return 1\n")
    return tree


def test_cache_hit_bit_identical(tmp_path):
    tree = _mini_tree(tmp_path)
    cache = str(tmp_path / "cache.json")
    cold = run_paths([str(tree)])                      # no cache at all
    warm0 = run_paths([str(tree)], cache=cache)        # cold, writes
    assert os.path.exists(cache)
    warm1 = run_paths([str(tree)], cache=cache)        # pure hits
    assert cold == warm0 == warm1                      # dataclass equality
    # An edit invalidates exactly that file.
    (tree / "b.py").write_text("import subprocess\n\n"
                               "def bad(cmd):\n"
                               "    return subprocess.run(cmd)\n")
    warm2 = run_paths([str(tree)], cache=cache)
    assert warm2 == run_paths([str(tree)])
    assert {f.path for f in warm2 if f.rule == "R007"} \
        == {"tools/a.py", "tools/b.py"}


def test_cache_rules_version_invalidates(tmp_path, monkeypatch):
    from cuvite_tpu.analysis import cache as cache_mod

    tree = _mini_tree(tmp_path)
    cache = str(tmp_path / "cache.json")
    run_paths([str(tree)], cache=cache)
    with open(cache, encoding="utf-8") as fh:
        data = json.load(fh)
    assert data["rules_version"] == cache_mod.rules_version()
    # A rules-set change (simulated version bump) must cold-start.
    monkeypatch.setattr(cache_mod, "rules_version", lambda: "different")
    lc = cache_mod.LintCache(cache)
    assert lc.entries == {}


def test_cache_corruption_degrades_to_cold(tmp_path):
    tree = _mini_tree(tmp_path)
    cache = str(tmp_path / "cache.json")
    with open(cache, "w", encoding="utf-8") as fh:
        fh.write("{not json")
    assert run_paths([str(tree)], cache=cache) == run_paths([str(tree)])


def test_cache_narrowed_rules_bypass(tmp_path):
    """A rules-subset run must not poison (or be served by) the cache."""
    from cuvite_tpu.analysis.rules import SubprocessNoTimeout

    tree = _mini_tree(tmp_path)
    cache = str(tmp_path / "cache.json")
    run_paths([str(tree)], cache=cache)        # full registry, cached
    only = run_paths([str(tree)], rules=[SubprocessNoTimeout()],
                     cache=cache)
    assert {f.rule for f in only} == {"R007"}
    full = run_paths([str(tree)], cache=cache)
    assert {f.rule for f in full} == {"R007"}


# ---------------------------------------------------------------------------
# Baseline hygiene: staleness report + --prune-baseline.


def test_stale_baseline_entries_and_prune(tmp_path):
    from cuvite_tpu.analysis.engine import (
        prune_baseline,
        stale_baseline_entries,
    )

    tree = _mini_tree(tmp_path)
    bl = str(tmp_path / "bl.json")
    findings = run_paths([str(tree)])
    write_baseline(bl, findings)
    # Fix the violation: the baseline entry goes stale.
    (tree / "a.py").write_text(
        (SUPPRESSIBLE % "").replace("subprocess.run(cmd)",
                                    "subprocess.run(cmd, timeout=60)"))
    now = run_paths([str(tree)])
    stale = stale_baseline_entries(now, load_baseline(bl))
    assert len(stale) == 1 and stale[0][0][1] == "R007"
    dropped = prune_baseline(bl, now)
    assert dropped == 1
    assert load_baseline(bl) == {}
    assert stale_baseline_entries(now, load_baseline(bl)) == []
    assert prune_baseline(bl, now) == 0      # idempotent


def test_prune_baseline_keeps_live_entries(tmp_path):
    from cuvite_tpu.analysis.engine import prune_baseline

    tree = _mini_tree(tmp_path)
    bl = str(tmp_path / "bl.json")
    findings = run_paths([str(tree)])
    write_baseline(bl, findings)
    assert prune_baseline(bl, findings) == 0
    new, old = apply_baseline(run_paths([str(tree)]), load_baseline(bl))
    assert new == [] and len(old) == len(findings)


def test_prune_and_staleness_are_scoped_to_linted_paths(tmp_path):
    """A subset run (lint.sh --changed, explicit paths) must treat
    entries for UNLINTED files as unknown — neither stale-reported nor
    pruned — or every subset run would steer the operator into deleting
    live grandfathered slots."""
    from cuvite_tpu.analysis.engine import (
        linted_rels,
        prune_baseline,
        stale_baseline_entries,
    )

    tree = _mini_tree(tmp_path)
    (tree / "c.py").write_text(SUPPRESSIBLE % "")   # second violation
    bl = str(tmp_path / "bl.json")
    write_baseline(bl, run_paths([str(tree)]))      # a.py + c.py slots
    # Subset run over ONE file: c.py's live entry must survive.
    subset = [str(tree / "a.py")]
    findings = run_paths(subset)
    linted = linted_rels(subset)
    assert linted == {"tools/a.py"}
    assert stale_baseline_entries(findings, load_baseline(bl),
                                  linted=linted) == []
    assert prune_baseline(bl, findings, linted=linted) == 0
    new, old = apply_baseline(run_paths([str(tree)]), load_baseline(bl))
    assert new == [] and len(old) == 2              # both still covered
    # The same subset WITHOUT the scope would have reported/pruned it.
    assert len(stale_baseline_entries(findings, load_baseline(bl))) == 1


def test_prune_baseline_cli_refuses_no_project(tmp_path):
    from cuvite_tpu.analysis.__main__ import main

    tree = _mini_tree(tmp_path)
    bl = str(tmp_path / "bl.json")
    write_baseline(bl, run_paths([str(tree)]))
    with pytest.raises(SystemExit):
        main([str(tree), "--baseline", bl, "--prune-baseline",
              "--no-project"])


def test_prune_baseline_cli(tmp_path, capsys):
    from cuvite_tpu.analysis.__main__ import main

    tree = _mini_tree(tmp_path)
    bl = str(tmp_path / "bl.json")
    write_baseline(bl, run_paths([str(tree)]))
    (tree / "a.py").write_text("x = 1\n")
    rc = main([str(tree), "--baseline", bl, "--prune-baseline"])
    assert rc == 0
    assert "pruned 1 stale baseline slot(s)" in capsys.readouterr().out
    assert load_baseline(bl) == {}


def test_selflint_reports_stale_count_in_text(tmp_path, capsys):
    from cuvite_tpu.analysis.__main__ import main

    tree = _mini_tree(tmp_path)
    bl = str(tmp_path / "bl.json")
    write_baseline(bl, run_paths([str(tree)]))
    (tree / "a.py").write_text("x = 1\n")
    rc = main([str(tree), "--baseline", bl])
    out = capsys.readouterr().out
    assert rc == 0 and "stale baseline slot(s)" in out


# ---------------------------------------------------------------------------
# SARIF output: schema shape + round-trip against the finding list.


def test_sarif_roundtrip(tmp_path, capsys):
    from cuvite_tpu.analysis.__main__ import main, to_sarif

    tree = _mini_tree(tmp_path)
    rc = main([str(tree), "--format", "sarif"])
    assert rc == 1                       # the R007 finding fails the gate
    doc = json.loads(capsys.readouterr().out)
    assert doc["version"] == "2.1.0"
    assert doc["$schema"].endswith("sarif-2.1.0.json")
    run = doc["runs"][0]
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert rule_ids >= set(RULE_IDS) | {"R017", "R018", "E000"}
    findings = run_paths([str(tree)])
    assert len(run["results"]) == len(findings)
    for res, f in zip(run["results"],
                      sorted(findings,
                             key=lambda f: (f.path, f.line, f.rule))):
        assert res["ruleId"] == f.rule
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == f.path
        assert loc["region"]["startLine"] == f.line
        assert loc["region"]["snippet"]["text"] == f.snippet
        assert res["partialFingerprints"]["graftlintFingerprint/v1"]
    # Fingerprints must be a pure function of (path, rule, snippet):
    # regenerating from the same findings is byte-identical.
    assert to_sarif(findings) == to_sarif(findings)
    # Severity -> SARIF level mapping (R007 is high -> error).
    assert run["results"][0]["level"] == "error"


def test_sarif_baselined_findings_are_excluded(tmp_path, capsys):
    from cuvite_tpu.analysis.__main__ import main

    tree = _mini_tree(tmp_path)
    bl = str(tmp_path / "bl.json")
    write_baseline(bl, run_paths([str(tree)]))
    rc = main([str(tree), "--format", "sarif", "--baseline", bl])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["runs"][0]["results"] == []
    assert doc["runs"][0]["properties"]["baselinedFindings"] >= 1


# ---------------------------------------------------------------------------
# Tier 3: jaxpr lint + compile-budget audit (the dynamic tier).  The
# audit runs the REAL entries at the representative small class — the
# same scenarios tools/compile_audit.py grades — plus the sabotage
# fixture proving B002 actually catches content-in-the-compile-key.

sys.path.insert(0, os.path.join(REPO, "tools"))


def test_compile_budget_audit_tier1(monkeypatch):
    """tools/compile_audit.py must pass on the current repo: observed
    compile set ⊆ the checked-in manifest, nothing recompiles on a
    content-only change, and the traced jaxprs carry no 64-bit ops,
    callbacks, or in-graph transfers."""
    monkeypatch.chdir(REPO)
    import compile_audit

    results, jaxpr_findings = compile_audit.run_audit()
    problems = [f.format() for r in results for f in r.findings]
    problems += [f.format() for f in jaxpr_findings]
    assert not problems, "\n".join(problems)


def test_compile_audit_sabotage_content_in_compile_key():
    """Thread batch content into a compile key (weights as a static
    argument) and assert the budget auditor catches it — the gate that
    replaces PR 10's by-hand measurement."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from cuvite_tpu.analysis.jaxpr_audit import audit_entry

    @functools.partial(jax.jit, static_argnames=("w",))
    def sabotaged(x, *, w):
        # content (the weight tuple) is a STATIC: every distinct batch
        # recompiles — exactly what pinning weights f32 prevents.
        return x * jnp.asarray(w, dtype=jnp.float32)

    def run(seed):
        w = tuple(float(v) for v in
                  np.random.default_rng(seed).uniform(0.5, 2.0, 4))
        sabotaged(np.ones(4, np.float32), w=w)

    res = audit_entry("sabotage", run,
                      {"modules": ["sabotaged"],
                       "content_independent": True})
    assert any(f.rule == "B002" for f in res.findings), res
    assert not res.ok


def test_compile_audit_sabotage_occupancy_in_compile_key():
    """ISSUE 20: sub-row OCCUPANCY (how many tenants landed in a packed
    row — batch content, like the weights) must never become a static.
    A sabotaged packed-run twin that threads the occupancy count into a
    static argument recompiles when the second audit run packs a
    different number of tenants — B002 fires."""
    import functools

    import jax
    import numpy as np

    from cuvite_tpu.analysis.jaxpr_audit import audit_entry

    @functools.partial(jax.jit, static_argnames=("n_occupied",))
    def sabotaged_packed(x, *, n_occupied):
        # occupancy as a STATIC: every distinct fill level recompiles —
        # exactly what pack_subrows' runtime sub_valid mask prevents.
        return x * (x.shape[0] // n_occupied)

    def run(seed):
        # The audit varies only the content seed; occupancy follows it
        # the way a skewed serving mix varies fill level batch to batch.
        n_occupied = 1 + (seed % 2)
        sabotaged_packed(np.ones(4, np.float32), n_occupied=n_occupied)

    res = audit_entry("sabotage-occupancy", run,
                      {"modules": ["sabotaged_packed"],
                       "content_independent": True})
    assert any(f.rule == "B002" for f in res.findings), res
    assert not res.ok


def test_compile_audit_missing_manifest_entry_fails_closed():
    from cuvite_tpu.analysis.jaxpr_audit import audit_entry

    res = audit_entry("ghost_entry", lambda seed: None, None)
    assert [f.rule for f in res.findings] == ["B001"]


def test_compile_audit_union_patterns_cover_shared_programs():
    """Which entry a shared program's compile lands on depends on run
    order (the serve path compiles the batched entries' programs when
    audited alone): matching must accept the UNION of the manifest's
    modules via extra_patterns, not just the entry's own."""
    import jax
    import numpy as np

    from cuvite_tpu.analysis.jaxpr_audit import audit_entry

    def shared_program(x):
        return x - 1

    jitted = jax.jit(shared_program)

    def run(seed):
        jitted(np.full(5, seed, np.float32))

    alone = audit_entry("other_entry", run,
                        {"modules": [], "content_independent": True})
    assert any(f.rule == "B001" for f in alone.findings)
    covered = audit_entry("other_entry", run,
                          {"modules": [], "content_independent": True},
                          extra_patterns=("shared_program",))
    assert not [f for f in covered.findings if f.rule == "B001"]


def test_compile_audit_unexpected_module_is_b001():
    import jax
    import numpy as np

    from cuvite_tpu.analysis.jaxpr_audit import audit_entry

    def interloper_program(x):
        return x + 1

    jitted = jax.jit(interloper_program)

    def run(seed):
        jitted(np.full(3, seed, np.float32))  # same shapes: one compile

    res = audit_entry("closed_set", run,
                      {"modules": ["something_else"],
                       "content_independent": True})
    rules = [f.rule for f in res.findings]
    assert "B001" in rules and "B002" not in rules


def test_jaxpr_lint_flags_wide_dtypes_and_callbacks():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cuvite_tpu.analysis.jaxpr_audit import lint_jaxpr

    def clean(x):
        return jnp.sum(x * 2)

    jaxpr = jax.make_jaxpr(clean)(np.ones(8, np.float32))
    assert lint_jaxpr(jaxpr, "clean") == []

    def with_callback(x):
        return jax.pure_callback(
            lambda v: np.asarray(v),
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    jaxpr = jax.make_jaxpr(with_callback)(np.ones(8, np.float32))
    hits = lint_jaxpr(jaxpr, "with_callback")
    assert [f.rule for f in hits] == ["J002"]
    assert hits[0].severity == "high"
    assert lint_jaxpr(jaxpr, "with_callback", allow=("J002",)) == []


def test_jaxpr_lint_recurses_into_subjaxprs():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cuvite_tpu.analysis.jaxpr_audit import lint_jaxpr

    def body(c):
        i, x = c
        y = jax.pure_callback(
            lambda v: np.asarray(v),
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return i + 1, y

    def looped(x):
        return jax.lax.while_loop(lambda c: c[0] < 3, body, (0, x))

    jaxpr = jax.make_jaxpr(looped)(jnp.ones(4, jnp.float32))
    assert any(f.rule == "J002"
               for f in lint_jaxpr(jaxpr, "looped"))
