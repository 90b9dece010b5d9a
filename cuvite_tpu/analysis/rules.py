"""graftlint per-file rule set R001..R016 + R022 + R029 (see
ANALYSIS.md for the catalogue; R017-R021 live in the project-tier
modules).

Each rule targets a hazard class this codebase has actually hit (or is
one refactor away from hitting): host syncs inside jitted code, jit
recompile traps, 64-bit dtype drift into the 32-bit device path,
collective-order divergence across hosts, mutation of caller-owned
buffers, non-exact reductions feeding modularity, unbounded child
processes in tools, host-global side effects in test fixtures, network
access outside the workloads fetch path (or without checksum
verification), device->host pulls in phase-transition code, Pallas
block shapes not derived from the static width-ladder constants, and
bench timing windows that close without forcing device completion,
full-slab sorts in coarsen/kernels outside the sanctioned coalesce
fallback chokepoint, compile/upload-per-job traps in serving queue
loops, bucket-plan construction inside serve/ dispatch loops (planning
belongs at pack time), direct wall-clock reads in serve/ outside
the injectable-clock plumbing (untestable deadlines), and resident-slab
mutation in stream//serve/ outside the apply_delta_slab chokepoint
(the donor-buffer aliasing trap).

Rules are heuristic by design: they trade completeness for a near-zero
false-positive rate on idiomatic code, and every remaining intentional
violation is handled by an inline ``# graftlint: disable=R###`` with a
justification comment, or by the checked-in baseline.
"""

from __future__ import annotations

import ast

from cuvite_tpu.analysis.engine import (
    _JIT_NAMES,
    Rule,
    dotted,
    register,
)

# Directories whose modules run (or build arrays for) the device path.
DEVICE_PATH_PREFIXES = (
    "cuvite_tpu/louvain/",
    "cuvite_tpu/kernels/",
    "cuvite_tpu/ops/",
)

# Host-blocking calls that must not appear in jit-reachable code: each
# one forces a device->host transfer (or a trace-time concretization
# error that only fires on the first run of a rarely-taken path).
HOST_SYNC_ATTRS = {"item", "block_until_ready", "tolist"}
HOST_SYNC_CALLS = {
    "float", "int", "bool",
    "np.asarray", "numpy.asarray", "np.array", "numpy.array",
    "jax.device_get",
}

# Host-side collective wrappers (cuvite_tpu.comm.multihost) plus the jax
# primitives they wrap: every host must reach these in the same order.
COLLECTIVE_NAMES = {
    "process_allgather", "allgather_varlen", "allreduce_sum_host",
    "allreduce_max_host", "gather_global", "broadcast_one_to_all",
    "sync_global_devices", "broadcast_host_local_array",
}

# Condition calls that are uniform across hosts by construction, so
# branching on them cannot diverge collective order.
UNIFORM_CONDITION_CALLS = {
    "is_distributed", "len", "isinstance", "issubclass", "bool", "int",
    "jax.process_count", "process_count", "hasattr",
}


def _in_device_path(sf) -> bool:
    return sf.rel.startswith(DEVICE_PATH_PREFIXES)


def _nodes_of_function(sf, info):
    """Nodes lexically inside ``info``'s body but not inside a nested
    def (those belong to the nested function)."""
    for node in ast.walk(info.node):
        if node is not info.node and sf.enclosing_function(node) is info:
            yield node


@register
class HostSyncInJit(Rule):
    id = "R001"
    severity = "high"
    title = "host-sync call reachable from a @jax.jit function"

    def check(self, sf):
        for node in sf.walk():
            if not isinstance(node, ast.Call):
                continue
            info = sf.enclosing_function(node)
            if info is None or not info.jit_reachable:
                continue
            name = dotted(node.func)
            label = None
            if name in HOST_SYNC_CALLS:
                label = f"{name}()"
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in HOST_SYNC_ATTRS \
                    and not node.args:
                label = f".{node.func.attr}()"
            if label is None:
                continue
            yield self.finding(
                sf, node,
                f"{label} in '{info.name}' (reachable from @jax.jit): "
                "forces a blocking device->host sync, or a trace-time "
                "concretization error on the first traced run")


def _is_none_check(test: ast.expr) -> bool:
    """``<expr> is None`` / ``is not None`` — trace-time structural
    dispatch (an operand is either a tracer or literally None), never a
    branch on traced VALUES, so R002 exempts it wholesale."""
    return (isinstance(test, ast.Compare)
            and len(test.ops) == 1
            and isinstance(test.ops[0], (ast.Is, ast.IsNot))
            and isinstance(test.comparators[0], ast.Constant)
            and test.comparators[0].value is None)


@register
class RecompileTrap(Rule):
    id = "R002"
    severity = "medium"
    title = "jit recompile trap (non-literal statics / traced branching)"

    def _check_statics(self, sf):
        from cuvite_tpu.analysis.engine import (
            _const_ints, _const_names, _jit_call,
        )

        for node in sf.walk():
            call = _jit_call(node)
            if call is None:
                continue
            for kw in call.keywords:
                if kw.arg == "static_argnames":
                    ok = _const_names(kw.value) is not None \
                        or isinstance(kw.value, ast.Name)
                    what = "static_argnames"
                elif kw.arg == "static_argnums":
                    ok = _const_ints(kw.value) is not None \
                        or isinstance(kw.value, ast.Name)
                    what = "static_argnums"
                else:
                    continue
                if not ok:
                    yield self.finding(
                        sf, kw.value,
                        f"{what} is not a literal int/str (tuple): "
                        "computed statics hide unhashable or array "
                        "values, which either crash dispatch or key the "
                        "compile cache on object identity (a recompile "
                        "per call)")

    def _check_branches(self, sf):
        for info in sf.functions:
            if not info.is_jit:
                continue
            traced = set(info.params) - info.static_names
            if not traced:
                continue
            for node in _nodes_of_function(sf, info):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                if _is_none_check(node.test):
                    continue
                names = {n.id for n in ast.walk(node.test)
                         if isinstance(n, ast.Name)}
                hot = sorted(names & traced)
                if hot:
                    yield self.finding(
                        sf, node,
                        f"Python branch on traced argument(s) "
                        f"{', '.join(hot)} of jitted '{info.name}': "
                        "concretizes the tracer (TracerBoolConversionError"
                        " at best, silent per-value recompiles via "
                        "static fallback at worst); use lax.cond/select "
                        "or mark the argument static")

    def check(self, sf):
        yield from self._check_statics(sf)
        yield from self._check_branches(sf)


_J64_ATTRS = {"jnp.int64", "jnp.float64", "jnp.uint64",
              "jax.numpy.int64", "jax.numpy.float64", "jax.numpy.uint64"}
_J64_NP_ATTRS = {"np.int64", "np.float64", "np.uint64",
                 "numpy.int64", "numpy.float64", "numpy.uint64"}
_J64_STRINGS = {"int64", "float64", "uint64"}
_JNP_PREFIXES = ("jnp.", "jax.numpy.")


def _is_64_dtype_arg(node: ast.AST) -> str | None:
    """'int64'-style label if ``node`` denotes a 64-bit dtype (string
    constant or np/numpy attribute; jnp attributes are reported by the
    attribute branch already), else None."""
    if isinstance(node, ast.Constant) and node.value in _J64_STRINGS:
        return str(node.value)
    name = dotted(node)
    if name in _J64_NP_ATTRS:
        return name
    return None


@register
class DtypeWidthDrift(Rule):
    id = "R003"
    severity = "medium"
    title = "64-bit device dtype in a 32-bit device-path module"

    def check(self, sf):
        if not _in_device_path(sf):
            return
        for node in sf.walk():
            if isinstance(node, ast.Attribute) and dotted(node) in _J64_ATTRS:
                yield self.finding(
                    sf, node,
                    f"{dotted(node)} in a device-path module: without "
                    "jax_enable_x64 this silently degrades to 32-bit "
                    "(corrupting packed keys / ids), and with it the "
                    "whole graph pays 2x memory; route widths through "
                    "the dtype policy (core.types) instead")
            elif isinstance(node, ast.Call):
                fname = dotted(node.func) or ""
                if fname.startswith(_JNP_PREFIXES):
                    for kw in node.keywords:
                        label = kw.arg == "dtype" \
                            and _is_64_dtype_arg(kw.value)
                        if label:
                            yield self.finding(
                                sf, kw.value,
                                f"dtype={label} passed to {fname} in a "
                                "device-path module: defeats the 32-bit "
                                "graph mode (see R003 notes in "
                                "ANALYSIS.md)")
                # .astype(<64-bit>) where the receiver is itself a jnp
                # construction — host np arrays cast with .astype(np.int64)
                # are plan-building code and stay out of scope.
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "astype" and node.args:
                    recv = node.func.value
                    rname = dotted(recv.func) \
                        if isinstance(recv, ast.Call) else dotted(recv)
                    label = _is_64_dtype_arg(node.args[0])
                    if label and rname and rname.startswith(_JNP_PREFIXES):
                        yield self.finding(
                            sf, node,
                            f".astype({label}) on a {rname} result in a "
                            "device-path module: defeats the 32-bit "
                            "graph mode (see R003 notes in ANALYSIS.md)")


def _condition_is_divergent(test: ast.expr) -> str | None:
    """Why a branch condition can differ between hosts, or None.

    Divergent: references process_index / process_id, or contains any
    call other than the known host-uniform predicates (a call result is
    runtime data the linter cannot prove replicated)."""
    for n in ast.walk(test):
        name = dotted(n) if isinstance(n, (ast.Name, ast.Attribute)) else None
        if name and name.split(".")[-1] in ("process_index", "process_id"):
            return f"condition references {name}"
        if isinstance(n, ast.Call):
            cname = dotted(n.func) or "<expr>"
            if cname.split(".")[-1] not in UNIFORM_CONDITION_CALLS \
                    and cname not in UNIFORM_CONDITION_CALLS:
                return f"condition depends on {cname}(...)"
    return None


@register
class CollectiveOrderDivergence(Rule):
    id = "R004"
    severity = "high"
    title = "collective call under a data-dependent or fallible branch"

    def check(self, sf):
        for node in sf.walk():
            if not isinstance(node, ast.Call):
                continue
            fname = dotted(node.func) or ""
            if fname.split(".")[-1] not in COLLECTIVE_NAMES:
                continue
            info = sf.enclosing_function(node)
            boundary = info.node if info is not None else None
            child = node
            for anc in sf.ancestors(node):
                if anc is boundary:
                    break
                if isinstance(anc, ast.Try):
                    yield self.finding(
                        sf, node,
                        f"collective {fname}() inside a try block: an "
                        "exception on one host skips its remaining "
                        "collectives while peers block in them — "
                        "deadlock, not an error message; hoist the "
                        "collective out or convert the failure into a "
                        "value every host agrees on")
                    break
                if isinstance(anc, (ast.If, ast.While)) \
                        and child is not anc.test:
                    why = _condition_is_divergent(anc.test)
                    if why:
                        yield self.finding(
                            sf, node,
                            f"collective {fname}() under a branch that "
                            f"may differ between hosts ({why}): hosts "
                            "disagreeing on whether to issue a "
                            "collective is the canonical multi-host "
                            "deadlock; make the condition a replicated "
                            "value or issue the collective "
                            "unconditionally")
                        break
                child = anc


_INPLACE_METHODS = {"fill", "sort", "resize", "partition", "put", "setfield"}


@register
class CallerBufferMutation(Rule):
    id = "R005"
    severity = "medium"
    title = "mutation of a caller-owned buffer argument"

    def check(self, sf):
        for info in sf.functions:
            # Pallas kernels receive mutable Refs — writing *_ref output
            # params is their calling convention, not a hazard.
            params = {p for p in info.params
                      if p not in ("self", "cls")
                      and not p.endswith("_ref")}
            if not params:
                continue
            for node in _nodes_of_function(sf, info):
                yield from self._check_node(sf, info, params, node)

    def _check_node(self, sf, info, params, node):
        def is_param(expr):
            return isinstance(expr, ast.Name) and expr.id in params

        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                # p.flags.writeable = ... — the caller's array changes
                # behaviour (later writes raise) as a side effect.
                if isinstance(tgt, ast.Attribute) \
                        and tgt.attr == "writeable" \
                        and isinstance(tgt.value, ast.Attribute) \
                        and tgt.value.attr == "flags" \
                        and is_param(tgt.value.value):
                    yield self.finding(
                        sf, node,
                        f"'{info.name}' flips writeable on its argument "
                        f"'{tgt.value.value.id}': the caller's buffer "
                        "changes behaviour behind its back — document "
                        "the contract and freeze the base chain, or "
                        "copy instead")
                elif isinstance(tgt, ast.Subscript) and is_param(tgt.value):
                    yield self.finding(
                        sf, node,
                        f"'{info.name}' writes in place into its "
                        f"argument '{tgt.value.id}': callers retaining "
                        "the array observe the mutation (and zero-copy "
                        "device aliases of it go stale)")
        elif isinstance(node, ast.AugAssign):
            tgt = node.target
            if isinstance(tgt, ast.Subscript) and is_param(tgt.value):
                yield self.finding(
                    sf, node,
                    f"'{info.name}' updates its argument "
                    f"'{tgt.value.id}' in place")
        elif isinstance(node, ast.Call):
            fname = dotted(node.func) or ""
            if fname in ("np.copyto", "numpy.copyto") and node.args \
                    and is_param(node.args[0]):
                yield self.finding(
                    sf, node,
                    f"'{info.name}' np.copyto()s into its argument "
                    f"'{node.args[0].id}'")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _INPLACE_METHODS \
                    and is_param(node.func.value):
                yield self.finding(
                    sf, node,
                    f"'{info.name}' calls .{node.func.attr}() on its "
                    f"argument '{node.func.value.id}' (in-place)")


_MOD_NAME = ("mod", "modularity", "q")
_SUM_CALLS = {"segment_sum", "sum"}
# Substrings of the assigned expression that mark the exact path (the
# ds_* double-single helpers / ops.exactsum); accum_dtype-style params
# are checked separately on the enclosing function.
_EXACT_MARKERS = ("ds_", "exactsum")


def _is_mod_name(name: str) -> bool:
    low = name.lower()
    if "modularity" in low:
        return True
    parts = low.split("_")
    return parts[0] in _MOD_NAME or parts[-1] in _MOD_NAME


@register
class InexactModularityReduction(Rule):
    id = "R006"
    severity = "medium"
    title = "non-exact reduction feeding a modularity accumulator"

    def check(self, sf):
        if not (sf.rel.startswith("cuvite_tpu/louvain/")
                or sf.rel.startswith("cuvite_tpu/evaluate/")):
            return
        for node in sf.walk():
            if not isinstance(node, ast.Assign):
                continue
            names = [t.id for t in node.targets if isinstance(t, ast.Name)]
            if not any(_is_mod_name(n) for n in names):
                continue
            sub = ast.dump(node.value)
            if any(m in sub for m in _EXACT_MARKERS):
                continue  # already on the exact path
            info = sf.enclosing_function(node)
            if info is not None and any(
                    "accum" in p or p == "adt" for p in info.params):
                continue  # dtype-policy-aware: width chosen by caller
            for call in ast.walk(node.value):
                if not isinstance(call, ast.Call):
                    continue
                fname = dotted(call.func) or (
                    call.func.attr if isinstance(call.func, ast.Attribute)
                    else "")
                if fname.split(".")[-1] in _SUM_CALLS:
                    yield self.finding(
                        sf, node,
                        f"modularity accumulator '{names[0]}' fed by "
                        f"{fname.split('.')[-1]}() without the exact "
                        "path: f32 tree sums lose ~log2(n)*2^-24 "
                        "relative — enough to flip the 1e-6 convergence "
                        "test at scale; use ops.exactsum (ds32) or an "
                        "accum_dtype-aware reduction")
                    break


_SUBPROCESS_BLOCKING = {
    "subprocess.run", "subprocess.call", "subprocess.check_call",
    "subprocess.check_output",
}


@register
class SubprocessNoTimeout(Rule):
    id = "R007"
    severity = "high"
    title = "blocking subprocess call without a timeout in tools/"

    def check(self, sf):
        if not sf.rel.startswith("tools/"):
            return
        for node in sf.walk():
            if not isinstance(node, ast.Call):
                continue
            fname = dotted(node.func)
            if fname not in _SUBPROCESS_BLOCKING:
                continue
            if any(kw.arg == "timeout" for kw in node.keywords):
                continue
            if any(kw.arg is None for kw in node.keywords):
                continue  # **kwargs may carry a timeout: cannot prove
            yield self.finding(
                sf, node,
                f"{fname}() without timeout=: a hung child (TPU client "
                "handshake, OOM-thrash) wedges the whole tool run "
                "forever; pass a generous timeout and handle "
                "TimeoutExpired loudly")


_EMPTYISH = (None, "", "0")


def _env_get_polarity(sf, call: ast.Call, test: ast.expr):
    """How the env-get GATES ``test``: True — the branch cannot be taken
    unless the variable is set to an opt-in value; False — the branch
    cannot be taken WHILE it is set (``not get(X)``: the else branch is
    then the opted-in one); None — cannot prove either (an ``or`` arm or
    truthy default lets the branch fire regardless, and unknown
    constructs are treated the same, conservatively).

    Polarity flips: ``not`` flips; ``== / is`` against None/''/'0' flips
    (``get(X) is None`` means NOT set); ``!= / is not`` against those
    keeps; against any other constant, equality keeps (``== '1'`` is an
    explicit opt-in value) and inequality flips (``!= '1'`` is true
    whenever the var is unset — opt-out, rephrased).  Only ``and``
    conjunctions may sit between the get and the test root."""
    defaults = list(call.args[1:2]) + [
        kw.value for kw in call.keywords if kw.arg == "default"]
    for d in defaults:
        if not (isinstance(d, ast.Constant) and d.value in _EMPTYISH):
            return None  # truthy (or unprovable) default: true while unset
    positive = True
    if call is test:
        return positive
    child = call
    for anc in sf.ancestors(call):
        if isinstance(anc, ast.UnaryOp) and isinstance(anc.op, ast.Not):
            positive = not positive
        elif isinstance(anc, ast.Compare):
            if not (anc.comparators and child is anc.left
                    and isinstance(anc.comparators[0], ast.Constant)):
                return None  # yoda/chained forms: cannot prove gating
            op, cmp_ = anc.ops[0], anc.comparators[0]
            emptyish = cmp_.value in _EMPTYISH
            if isinstance(op, (ast.Eq, ast.Is)):
                positive ^= emptyish
            elif isinstance(op, (ast.NotEq, ast.IsNot)):
                positive ^= not emptyish
            else:
                return None
        elif isinstance(anc, ast.BoolOp):
            if not isinstance(anc.op, ast.And):
                return None  # an `or` arm bypasses the env var
        else:
            return None  # wrapped in a call/ifexp/...: cannot prove
        child = anc
        if anc is test:
            break
    return positive


def _opt_in_gated(sf, node) -> bool:
    """True if an ancestor ``if`` gates ``node`` on an os.environ.get /
    os.getenv whose polarity matches the BRANCH holding ``node``: the
    ``if`` body needs positive polarity (the opt-in idiom), the ``else``
    branch needs negative (the else of ``if not get(X)`` runs only when
    X is set).  Everything else — opt-OUT spellings (``not get(X)``,
    ``get(X) is None``, ``get(X) != '1'``), the else of an opt-IN check
    (runs by default when unset!), truthy defaults — does not count."""
    prev = node
    for anc in sf.ancestors(node):
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
            break
        if isinstance(anc, ast.If) and prev is not anc.test:
            in_body = any(prev is s for s in anc.body)
            in_orelse = any(prev is s for s in anc.orelse)
            for n in ast.walk(anc.test):
                if isinstance(n, ast.Call):
                    cname = dotted(n.func) or ""
                    if cname not in ("os.environ.get", "os.getenv") \
                            and not cname.endswith("environ.get"):
                        continue
                    pol = _env_get_polarity(sf, n, anc.test)
                    if (in_body and pol is True) \
                            or (in_orelse and pol is False):
                        return True
        prev = anc
    return False


@register
class HostGlobalTestSideEffect(Rule):
    id = "R008"
    severity = "high"
    title = "host-global side effect in tests without opt-in gating"

    def check(self, sf):
        if not (sf.rel.startswith("tests/")
                or sf.rel.endswith("conftest.py")):
            return
        for node in sf.walk():
            if not isinstance(node, ast.Call):
                continue
            fname = dotted(node.func)
            if fname == "open":
                target = node.args[0] if node.args else None
                mode = None
                if len(node.args) > 1:
                    mode = node.args[1]
                for kw in node.keywords:
                    if kw.arg == "mode":
                        mode = kw.value
                if not (isinstance(target, ast.Constant)
                        and isinstance(target.value, str)
                        and target.value.startswith("/proc/sys")):
                    continue
                if not (isinstance(mode, ast.Constant)
                        and isinstance(mode.value, str)
                        and any(c in mode.value for c in "wa+")):
                    continue
                if _opt_in_gated(sf, node):
                    continue
                yield self.finding(
                    sf, node,
                    f"sysctl write ({target.value}) in a test fixture "
                    "without an opt-in env gate: a HOST-GLOBAL knob "
                    "silently changed for everything else on the "
                    "machine; gate it on an explicit CUVITE_*=1 opt-in "
                    "and restore the prior value at session finish")
            elif fname == "os.putenv":
                if _opt_in_gated(sf, node):
                    continue
                yield self.finding(
                    sf, node,
                    "os.putenv() in tests bypasses os.environ "
                    "bookkeeping (leaks into every child, invisible to "
                    "os.environ readers); assign os.environ[...] "
                    "instead, or gate behind an opt-in")


# The ONE module allowed to open network connections: the workloads
# dataset registry's fetch path (which must checksum what it downloads).
NETWORK_ALLOWED_FILE = "cuvite_tpu/workloads/registry.py"

# Call names that open a network connection.  Matched on the dotted name
# (or its last attribute for the bare-import spellings).
_NET_CALL_NAMES = {
    "urlopen", "urlretrieve",  # urllib.request.* / bare from-imports
    "socket.create_connection", "ftplib.FTP",
    "http.client.HTTPConnection", "http.client.HTTPSConnection",
}
_NET_CALL_PREFIXES = ("urllib.request.", "requests.")

# Evidence that a function verifies what it downloaded: any call whose
# name mentions a digest or an explicit checksum/verify helper.
_CHECKSUM_MARKERS = ("sha256", "sha512", "sha1", "md5", "blake2",
                     "checksum", "verify")

_SUBPROCESS_ANY = _SUBPROCESS_BLOCKING | {"subprocess.Popen"}
_DOWNLOADER_TOOLS = {"curl", "wget", "aria2c", "scp", "rsync"}


def _is_net_call(name: str | None) -> bool:
    if not name:
        return False
    return (name in _NET_CALL_NAMES
            or name.split(".")[-1] in ("urlopen", "urlretrieve")
            or name.startswith(_NET_CALL_PREFIXES))


def _subprocess_downloader(node: ast.Call) -> str | None:
    """The downloader binary name if this subprocess call shells out to
    one (list or string first argument), else None."""
    if not node.args:
        return None
    arg = node.args[0]
    cands = []
    if isinstance(arg, (ast.List, ast.Tuple)):
        cands = [el.value for el in arg.elts
                 if isinstance(el, ast.Constant) and isinstance(el.value, str)]
    elif isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        cands = arg.value.split()
    for c in cands:
        base = c.rsplit("/", 1)[-1]
        if base in _DOWNLOADER_TOOLS:
            return base
    return None


@register
class NetworkOutsideRegistry(Rule):
    id = "R009"
    severity = "high"
    title = "network call outside the workloads fetch path, or a " \
            "download without checksum verification"

    def check(self, sf):
        for node in sf.walk():
            if not isinstance(node, ast.Call):
                continue
            fname = dotted(node.func)
            if _is_net_call(fname):
                if sf.rel != NETWORK_ALLOWED_FILE:
                    yield self.finding(
                        sf, node,
                        f"network call {fname}() outside "
                        f"{NETWORK_ALLOWED_FILE}: dataset fetches live in "
                        "the registry (offline rigs must fall back to the "
                        "synthesizer, and every download must be "
                        "checksum-verified there)")
                    continue
                info = sf.enclosing_function(node)
                calls = info.calls if info is not None else set()
                if not any(any(m in c.lower() for m in _CHECKSUM_MARKERS)
                           for c in calls):
                    yield self.finding(
                        sf, node,
                        f"download via {fname}() without checksum "
                        "verification in the same function: a truncated "
                        "or tampered artifact would convert silently; "
                        "hash the stream (hashlib.sha256) and verify "
                        "before use")
            elif dotted(node.func) in _SUBPROCESS_ANY:
                tool = _subprocess_downloader(node)
                if tool is not None:
                    yield self.finding(
                        sf, node,
                        f"subprocess download via '{tool}': shelling out "
                        "skips the registry's checksum verification and "
                        "offline fallback; use "
                        "cuvite_tpu.workloads.registry.fetch instead")


# Modules that carry device-resident phase-transition state (the slab
# that coarsen/device.py keeps in HBM across phases).  A stray host
# materialization here re-introduces the O(E) PCIe round-trip the device
# coarsener exists to remove — the regression class ISSUE 3 closed.
PHASE_TRANSITION_PREFIXES = (
    "cuvite_tpu/louvain/",
    "cuvite_tpu/coarsen/",
)

# Call spellings that pull a device array to the host wholesale.
_HOST_PULL_CALLS = {"jax.device_get"}
# np.asarray/np.array of a bare name that follows the device-array naming
# convention in these modules (slab/label arrays are *_d / *_dev /
# labels*).  Attributes and other expressions are out of scope: host plan
# arrays are routinely np.asarray'd during plan construction, and flagging
# them would bury the signal (near-zero-false-positive contract).
_HOST_MATERIALIZE_CALLS = {"np.asarray", "numpy.asarray",
                           "np.array", "numpy.array"}
_DEVICE_NAME_SUFFIXES = ("_dev", "_d")
_DEVICE_NAME_PREFIXES = ("labels",)


@register
class PallasLiteralBlockShape(Rule):
    id = "R011"
    severity = "medium"
    title = "Pallas BlockSpec block shape with a hard-coded dimension"

    # Unit dims are layout plumbing ((1, tile) vectors, (D, 1) rows), not a
    # tile-size decision; anything else must be a NAME bound to the static
    # width-ladder constants (DEFAULT_BUCKETS-derived D, the VMEM-budgeted
    # tile, LANE) so a ladder retune cannot leave a kernel silently
    # recompiling per width or overflowing VMEM with a stale literal.
    _ALLOWED_LITERALS = (1,)

    def check(self, sf):
        if not _in_device_path(sf):
            return
        for node in sf.walk():
            if not isinstance(node, ast.Call):
                continue
            fname = dotted(node.func) or ""
            if fname.split(".")[-1] != "BlockSpec":
                continue
            if not node.args:
                continue  # memory_space-only spec: no block shape
            shape = node.args[0]
            if not isinstance(shape, (ast.Tuple, ast.List)):
                continue
            for el in shape.elts:
                if isinstance(el, ast.Constant) \
                        and isinstance(el.value, int) \
                        and el.value not in self._ALLOWED_LITERALS:
                    yield self.finding(
                        sf, el,
                        f"BlockSpec block dimension {el.value} is a "
                        "hard-coded literal: block shapes must be derived "
                        "from the static width-ladder constants "
                        "(DEFAULT_BUCKETS widths / PALLAS_MAX_WIDTH / "
                        "LANE / the VMEM-budgeted tile) — a stale literal "
                        "silently recompiles per width class or blows "
                        "VMEM when the ladder is retuned")


@register
class DeviceArrayHostPull(Rule):
    id = "R010"
    severity = "medium"
    title = "device->host pull of a device-resident array in " \
            "phase-transition code"

    def check(self, sf):
        if not sf.rel.startswith(PHASE_TRANSITION_PREFIXES):
            return
        for node in sf.walk():
            if not isinstance(node, ast.Call):
                continue
            fname = dotted(node.func)
            if fname in _HOST_PULL_CALLS:
                yield self.finding(
                    sf, node,
                    f"{fname}() in a phase-transition module: a device->"
                    "host pull here puts O(E)/O(V) bytes back on the PCIe "
                    "path the device-resident coarsening removed; keep "
                    "the slab in HBM.  Scalar/stat syncs and THE final "
                    "label gather are the allowed exceptions — carry an "
                    "inline '# graftlint: disable=R010' with a "
                    "justification")
            elif fname in _HOST_MATERIALIZE_CALLS and node.args:
                arg = node.args[0]
                if isinstance(arg, ast.Name) and (
                        arg.id.endswith(_DEVICE_NAME_SUFFIXES)
                        or arg.id.startswith(_DEVICE_NAME_PREFIXES)):
                    yield self.finding(
                        sf, node,
                        f"{fname}({arg.id}) materializes a device-"
                        "resident array (by naming convention) on the "
                        "host inside phase-transition code; gather "
                        "scalars instead, or justify with an inline "
                        "disable (the final label gather is the "
                        "allowlisted case)")


# ---------------------------------------------------------------------------
# R012: async-dispatch mistiming in bench/tool timing windows (ISSUE 6).
# Every recorded perf number comes from a time.perf_counter() pair in
# tools/ or the bench harness; jax dispatch is ASYNC, so a window that
# directly dispatches device work and closes without forcing completion
# records launch latency, not execution time (the round-8 exchange
# microbenchmark was nearly rewritten with exactly this bug).

_TIMING_SCOPE_PREFIX = "tools/"
_TIMING_SCOPE_FILES = ("cuvite_tpu/workloads/bench.py",)
_PERF_COUNTER_CALLS = {"time.perf_counter", "perf_counter"}
# Evidence the window forces device completion: block_until_ready, or a
# readback of the value, which blocks just as hard.
_TIMING_SYNC_CALLS = {
    "float", "int", "bool",
    "np.asarray", "numpy.asarray", "np.array", "numpy.array",
    "jax.device_get", "jax.block_until_ready", "block_until_ready",
}
_TIMING_SYNC_ATTRS = {"block_until_ready", "item", "tolist"}
# Direct device-dispatch evidence.  Conservative by design: jnp ops,
# explicit uploads, and in-file jit-bound names.  Calls into opaque
# callables (louvain_phases, a passed-in fn) are NOT flagged — the
# callee may sync internally, and flagging them would bury the signal.
_DISPATCH_PREFIXES = ("jnp.", "jax.numpy.")
_DISPATCH_CALLS = {"jax.device_put"}


@register
class UnsyncedTimingWindow(Rule):
    id = "R012"
    severity = "medium"
    title = "perf_counter timing window closes without forcing device " \
            "completion"

    def _jit_names(self, sf) -> set:
        names = {info.name for info in sf.functions if info.is_jit}
        names.update(sf.jit_wrapped)
        for node in sf.walk():
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and dotted(node.value.func) in _JIT_NAMES:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        names.add(t.id)
        return names

    def check(self, sf):
        if not (sf.rel.startswith(_TIMING_SCOPE_PREFIX)
                or sf.rel in _TIMING_SCOPE_FILES):
            return
        jit_names = self._jit_names(sf)
        opens: dict = {}    # (scope id, var name) -> [linenos]
        closes: list = []   # (scope, var name, BinOp node)
        calls: dict = {}    # scope id -> [Call nodes]
        for node in sf.walk():
            scope = sf.enclosing_function(node)
            key = id(scope)
            if isinstance(node, ast.Call):
                calls.setdefault(key, []).append(node)
                continue
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and dotted(node.value.func) in _PERF_COUNTER_CALLS:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        opens.setdefault((key, t.id), []).append(
                            node.lineno)
            elif isinstance(node, ast.BinOp) \
                    and isinstance(node.op, ast.Sub) \
                    and isinstance(node.left, ast.Call) \
                    and dotted(node.left.func) in _PERF_COUNTER_CALLS \
                    and isinstance(node.right, ast.Name):
                closes.append((key, node.right.id, node))
        for key, var, close in closes:
            begins = [ln for ln in opens.get((key, var), ())
                      if ln < close.lineno]
            if not begins:
                continue  # window opened elsewhere (param, outer scope)
            begin = max(begins)
            inside = [c for c in calls.get(key, ())
                      if begin < c.lineno < close.lineno]
            dispatch = None
            last_dispatch_ln = None
            sync_lns = []
            for c in inside:
                fname = dotted(c.func) or ""
                if fname in _TIMING_SYNC_CALLS or (
                        isinstance(c.func, ast.Attribute)
                        and c.func.attr in _TIMING_SYNC_ATTRS):
                    # end_lineno: a wrapped readback whose argument
                    # spans lines (block_until_ready(\n jnp.dot(...)))
                    # still encloses the dispatch it forces.
                    sync_lns.append(getattr(c, "end_lineno", None)
                                    or c.lineno)
                    continue
                if fname.startswith(_DISPATCH_PREFIXES) \
                        or fname in _DISPATCH_CALLS \
                        or (isinstance(c.func, ast.Name)
                            and c.func.id in jit_names):
                    dispatch = dispatch or fname or c.func.id
                    if last_dispatch_ln is None \
                            or c.lineno > last_dispatch_ln:
                        last_dispatch_ln = c.lineno
            # Sync evidence must not PRECEDE the last dispatch: a
            # readback before the dispatch forces nothing, and bare
            # int()/float() on host values are everywhere in bench code.
            # >= keeps same-line wrapping (float(jnp.dot(...))) clean.
            synced = dispatch is not None and any(
                ln >= last_dispatch_ln for ln in sync_lns)
            if dispatch and not synced:
                yield self.finding(
                    sf, close,
                    f"timing window ({var} opened line {begin}) times "
                    f"the device dispatch '{dispatch}' but closes "
                    "without forcing completion (block_until_ready / a "
                    "readback): jax dispatch is async, so this records "
                    "launch latency, not execution time")


# ---------------------------------------------------------------------------
# R013: the coalesce sort tax must not creep back (ISSUE 8).
# BASELINE.md round-7 measured the full-slab lax.sort as THE cost of
# device-resident coarsening (coarsen_s 3.4 s -> 65.0 s at scale 20 on
# CPU: above ~2^15 padded vertices the packed int32 key no longer fits
# and lax.sort degrades to its slowest variadic comparator).  The ONLY
# sanctioned full-slab sort for the coalesce is the chokepoint
# ops/segment.py::coalesced_runs (a packed sort via
# sort_edges_by_vertex_comm).  A new direct sort in coarsen/ or kernels/
# would bypass it — silently re-imposing the tax.  The scope
# deliberately covers the device re-binner (coarsen/rebin.py, ISSUE 19),
# which exists precisely to AVOID per-phase sorts, so a lax.sort
# creeping into it is the regression this rule is for.

_SLAB_SORT_SCOPE = (
    "cuvite_tpu/coarsen/",
    "cuvite_tpu/kernels/",
)
_SLAB_SORT_CALLS = {
    "jax.lax.sort", "lax.sort",
    "jax.lax.sort_key_val", "lax.sort_key_val",
    "jnp.sort", "jnp.argsort", "jnp.lexsort",
    "jax.numpy.sort", "jax.numpy.argsort", "jax.numpy.lexsort",
}


@register
class SlabSortOutsideChokepoint(Rule):
    id = "R013"
    severity = "high"
    title = "full-slab device sort in coarsen/ or kernels/ outside the " \
            "sanctioned coalesce chokepoint"

    def check(self, sf):
        if not sf.rel.startswith(_SLAB_SORT_SCOPE):
            return
        for node in sf.walk():
            if not isinstance(node, ast.Call):
                continue
            fname = dotted(node.func)
            if fname in _SLAB_SORT_CALLS:
                yield self.finding(
                    sf, node,
                    f"{fname}() in a coarsen/kernel module: full-slab "
                    "sorts are the round-7 coarsening tax and live ONLY "
                    "behind ops/segment.coalesced_runs (the sanctioned "
                    "chokepoint); route through it — or carry an "
                    "inline '# graftlint: disable=R013' with a "
                    "justification for a genuinely non-slab sort")


# ---------------------------------------------------------------------------
# R014: compile-per-job / upload-per-job traps in serving queue loops
# (ISSUE 9).  The batched serving win rests on ONE compiled program per
# (slab class, B) and ONE device placement per packed batch — both live
# in louvain/batched.py at module scope.  A `jax.jit`/`jax.vmap` built
# inside a serve/ queue loop creates a FRESH callable per iteration
# (jit caches per callable identity, so every job recompiles), and a
# per-job `jax.device_put` re-uploads what the batched driver would
# place once per batch.  Either silently erases the amortization the
# subsystem exists for, without changing any result — exactly the class
# of regression a lint must catch, because no test output changes.

_SERVE_SCOPE = ("cuvite_tpu/serve/",)
# The PACKER path (ISSUE 20): the pack/prepare/unpack stage functions
# of the batched driver and the slab packers hold the same per-batch
# amortization contract as the serve/ queue loops — one upload, one
# plan build, zero jit construction per BATCH, however many tenants a
# merged sub-row batch carries.  Scope is per-FUNCTION (pack_*,
# prepare_*, unpack_*), not per-module: the phase loops in the same
# files legitimately run jax calls per iteration.
_PACKER_SCOPE = ("cuvite_tpu/louvain/batched.py", "cuvite_tpu/core/batch.py")
_PACKER_FUNC_PREFIXES = ("pack_", "prepare_", "unpack_")
_SERVE_LOOP_TRAPS = {
    "jax.jit", "jax.vmap", "jax.pmap",
    "jax.device_put", "jnp.asarray", "jax.numpy.asarray",
}


def _serve_loop_calls(sf, names):
    """(node, fname) for every call of ``names`` lexically inside a
    for/while loop of a serve/ module, or of a packer-path function
    (pack_*/prepare_*/unpack_* in the batched driver and slab packer)
    — the shared traversal of the per-job amortization-trap rules
    (R014 compile/upload, R015 plan construction), so their loop/scope
    semantics cannot drift."""
    in_serve = sf.rel.startswith(_SERVE_SCOPE)
    if not in_serve and sf.rel not in _PACKER_SCOPE:
        return
    seen: set = set()
    for loop in sf.walk():
        if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
            continue
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            fname = dotted(node.func)
            if fname in names:
                if not in_serve:
                    info = sf.enclosing_function(node)
                    if info is None or not info.name.startswith(
                            _PACKER_FUNC_PREFIXES):
                        continue
                seen.add(id(node))
                yield node, fname


@register
class ServeLoopCompileTrap(Rule):
    id = "R014"
    severity = "high"
    title = "jit/vmap construction or per-job device upload inside a " \
            "serve/ queue loop"

    def check(self, sf):
        for node, fname in _serve_loop_calls(sf, _SERVE_LOOP_TRAPS):
            what = ("recompiles per job (jit caches per "
                    "callable identity)"
                    if fname in _JIT_NAMES
                    or fname in ("jax.vmap", "jax.pmap")
                    else "re-uploads per job")
            yield self.finding(
                sf, node,
                f"{fname}() inside a serve/ queue loop {what}: "
                "the batched serving contract is ONE compiled "
                "program per (slab class, B) at module scope "
                "(louvain/batched.py) and ONE device placement "
                "per packed batch (run_batched); hoist it out "
                "of the loop, or justify with an inline "
                "'# graftlint: disable=R014'")


# ---------------------------------------------------------------------------
# R015: bucket-plan construction inside serve/ dispatch loops (ISSUE
# 10).  The batched BUCKETED engine's whole premise is that planning
# happens ONCE per packed batch, at pack time: run_batched calls
# core.batch.batch_bucket_plans (one O(sum E) host pass covering every
# row) before any device work.  A BucketPlan.build /
# build_stacked_plans / batch_bucket_plans call inside a serve/
# for-or-while loop is the plan-PER-JOB trap: it rebuilds O(E) gather
# matrices per tenant per dispatch, turning the pack-time amortization
# into per-job host work — results unchanged, throughput silently
# gone, exactly the regression class R014 guards on the compile side.
# Since ISSUE 19 coarse phases re-bin their plans ON DEVICE inside the
# compiled phase program (coarsen/rebin.py::rebin_plan /
# device_rebin_plan — the sanctioned in-loop planner, deliberately NOT
# in the trap set): a serve loop that calls the host builders per
# phase is silently falling back from that path.

_PLAN_BUILD_CALLS = {
    "BucketPlan.build", "bucketed.BucketPlan.build",
    "build_stacked_plans", "bucketed.build_stacked_plans",
    "batch_bucket_plans", "batch.batch_bucket_plans",
}


@register
class ServeLoopPlanTrap(Rule):
    id = "R015"
    severity = "high"
    title = "bucket-plan construction inside a serve/ dispatch loop " \
            "(planning belongs at pack time)"

    def check(self, sf):
        for node, fname in _serve_loop_calls(sf, _PLAN_BUILD_CALLS):
            yield self.finding(
                sf, node,
                f"{fname}() inside a serve/ dispatch loop builds "
                "bucket plans per job: planning belongs at PACK "
                "time — one batch_bucket_plans call per packed "
                "batch inside run_batched (louvain/batched.py) — "
                "and coarse-phase re-planning belongs ON DEVICE "
                "(coarsen/rebin.py::device_rebin_plan, the "
                "sanctioned in-loop re-binner); hoist the host "
                "plan construction out of the loop, or justify "
                "with an inline '# graftlint: disable=R015'")


# ---------------------------------------------------------------------------
# R016: direct wall-clock reads in serve/ outside the injectable-clock
# plumbing (ISSUE 11).  Every deadline in the serving layer — linger,
# job deadline shedding, admission retry_after_s, retry backoff — runs
# on an injected ``clock`` so tests can drive it without sleeping.  A
# ``time.monotonic()`` / ``time.time()`` call added directly in serve/
# re-introduces the untestable-deadline trap: the behavior it gates can
# only be exercised by actually sleeping through it (slow, flaky), and
# a fake-clock test silently no longer covers the path.  The ONE
# sanctioned wall-clock site is serve/clock.py (the plumbing the
# injectable defaults come from); ``time.perf_counter()`` stays
# allowlisted everywhere — busy-window timing measures real elapsed
# work and is never compared against an injectable deadline.

_SERVE_CLOCK_MODULE = "cuvite_tpu/serve/clock.py"
# time.monotonic / time.time by dotted name, plus the bare from-import
# spelling of monotonic (a bare `time()` call is left out: it is far
# more likely to be a local callable than the stdlib clock).
_WALL_CLOCK_CALLS = {"time.monotonic", "time.time", "monotonic"}


@register
class ServeThreadingOutsideSeam(Rule):
    id = "R022"
    severity = "high"
    title = "threading primitive constructed directly in serve/ " \
            "outside the sync seam"

    # The seam module itself is the ONE sanctioned construction site.
    _SEAM = "cuvite_tpu/serve/sync.py"
    _PRIMS = ("Thread", "Lock", "RLock", "Event", "Condition",
              "Semaphore", "BoundedSemaphore", "Barrier")

    def check(self, sf):
        # R022 (ISSUE 14): every lock/event/thread the serving layer
        # creates must come from serve/sync.py's factories — a plain
        # threading.X in production AND a scheduler-backed twin under
        # the concheck cooperative scheduler (graftlint tier 4).  A
        # direct `threading.Lock()` in serve/ silently EXITS that
        # seam: the daemon still works, but concheck can no longer
        # serialize or replay schedules through the primitive, so the
        # exact race/deadlock classes tier 4 exists to catch go back
        # to reviewer vigilance.  PR 13 made the seam a convention;
        # this rule makes it a checked invariant.
        if not sf.rel.startswith(_SERVE_SCOPE) or sf.rel == self._SEAM:
            return
        aliases = {"threading"}
        bare: set = set()
        for node in sf.walk():
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "threading":
                        aliases.add(a.asname or "threading")
            elif isinstance(node, ast.ImportFrom) \
                    and node.module == "threading":
                for a in node.names:
                    if a.name in self._PRIMS:
                        bare.add(a.asname or a.name)
        for node in sf.walk():
            if not isinstance(node, ast.Call):
                continue
            fname = dotted(node.func)
            if fname is None:
                continue
            hit = None
            if "." in fname:
                mod, _, attr = fname.rpartition(".")
                if mod in aliases and attr in self._PRIMS:
                    hit = fname
            elif fname in bare:
                hit = fname
            if hit is None:
                continue
            yield self.finding(
                sf, node,
                f"{hit}() constructed directly in a serve/ module: "
                "serve/ synchronization primitives must come from the "
                "serve/sync.py factories (sync.Lock/RLock/Event/"
                "Condition/Thread) so the concheck cooperative "
                "scheduler (graftlint tier 4) can serialize, replay "
                "and race-check them; a raw threading primitive is "
                "invisible to every tier-4 schedule — use the seam, "
                "or justify with an inline '# graftlint: disable=R022'")


@register
class ServeWallClockOutsidePlumbing(Rule):
    id = "R016"
    severity = "high"
    title = "direct wall-clock read in serve/ outside the " \
            "injectable-clock plumbing"

    def check(self, sf):
        if not sf.rel.startswith(_SERVE_SCOPE) \
                or sf.rel == _SERVE_CLOCK_MODULE:
            return
        for node in sf.walk():
            if not isinstance(node, ast.Call):
                continue
            fname = dotted(node.func)
            if fname in _WALL_CLOCK_CALLS:
                yield self.finding(
                    sf, node,
                    f"{fname}() read directly in a serve/ module: "
                    "serving deadlines must run on the INJECTABLE "
                    "clock (serve/clock.py plumbing, threaded as the "
                    "clock=/sleep= parameters) or they become "
                    "untestable without real sleeps; call the injected "
                    "clock instead (time.perf_counter busy-timing is "
                    "allowlisted, and a reference like "
                    "clock=time.monotonic as a DEFAULT is fine — only "
                    "direct calls are flagged)")


# ---------------------------------------------------------------------------
# R029: resident-slab mutation outside the apply_delta_slab chokepoint
# (ISSUE 17).  A StreamSession keeps its slab (src/dst/w) RESIDENT on
# device between delta batches, and the serving pool hands the same
# arrays to every subsequent request — so those buffers are live
# references, not scratch.  The streaming contract routes every edit
# through ONE jitted chokepoint, stream/delta.py::apply_delta_slab
# (sentinel-retire + masked append + re-coalesce, pow2 class
# preserved), with grow_slab/shrink_slab as the only sanctioned class
# reshapes.  An ``x.at[...].set(...)`` written directly in stream/ or
# serve/ re-edits the slab OUTSIDE that seam: it silently forks the
# canonical form the bit-equality tests pin (ordering, padding
# sentinels, the 2m fixup), and under donation
# (``jit(..., donate_argnums=...)``) it is the donor-buffer aliasing
# trap outright — the resident reference the pool still holds now
# points at a donated (invalidated) buffer, which jax surfaces as a
# delete-buffer error only on the NEXT request that touches the
# tenant.  Both spellings are flagged; delta.py itself (the chokepoint)
# is exempt by path.

_STREAM_SLAB_SCOPE = (
    "cuvite_tpu/stream/",
    "cuvite_tpu/serve/",
)
_STREAM_SLAB_CHOKEPOINT = "cuvite_tpu/stream/delta.py"
# .at[...] update methods (jax.numpy.ndarray.at): every one writes.
_AT_UPDATE_METHODS = {
    "set", "add", "subtract", "multiply", "mul", "divide", "div",
    "power", "min", "max", "apply",
}


def _is_at_indexed_update(node: ast.Call) -> bool:
    """Matches ``<expr>.at[<idx>].<method>(...)`` — the functional
    index-update spelling, which on a RESIDENT buffer is still a slab
    edit even though it returns a copy."""
    f = node.func
    return (isinstance(f, ast.Attribute)
            and f.attr in _AT_UPDATE_METHODS
            and isinstance(f.value, ast.Subscript)
            and isinstance(f.value.value, ast.Attribute)
            and f.value.value.attr == "at")


@register
class ResidentSlabMutationOutsideChokepoint(Rule):
    id = "R029"
    severity = "high"
    title = "resident-slab mutation in stream//serve/ outside the " \
            "apply_delta_slab chokepoint (donor-buffer aliasing trap)"

    def check(self, sf):
        if not sf.rel.startswith(_STREAM_SLAB_SCOPE) \
                or sf.rel == _STREAM_SLAB_CHOKEPOINT:
            return
        for node in sf.walk():
            if not isinstance(node, ast.Call):
                continue
            if _is_at_indexed_update(node):
                yield self.finding(
                    sf, node,
                    f".at[...].{node.func.attr}() in a stream//serve/ "
                    "module: resident slabs are edited ONLY through "
                    "stream/delta.py::apply_delta_slab (sentinel-retire "
                    "+ masked append + re-coalesce, one jitted "
                    "chokepoint) so the canonical form the delta-vs-"
                    "rebuild bit-equality tests pin cannot fork; route "
                    "the edit through the chokepoint, or justify a "
                    "genuinely non-slab update with an inline "
                    "'# graftlint: disable=R029'")
                continue
            fname = dotted(node.func)
            if fname in _JIT_NAMES:
                for kw in node.keywords:
                    if kw.arg in ("donate_argnums", "donate_argnames"):
                        yield self.finding(
                            sf, kw.value,
                            f"jit({kw.arg}=...) in a stream//serve/ "
                            "module: donating a RESIDENT buffer "
                            "invalidates the reference the stream pool "
                            "still holds — the next request on the "
                            "tenant reads a deleted buffer; resident "
                            "slabs flow through apply_delta_slab "
                            "without donation, or justify with an "
                            "inline '# graftlint: disable=R029'")
