"""Tier 6 (static half) — index-width/overflow analysis (R026-R028).

ROADMAP item 1's unlock is Friendster (1.8 B undirected edges, so the
directed slab and 2m both clear 2^31) and R-MAT scale 28, yet the hot
paths are deliberately 32-bit: the reference ships ``-DUSE_32_BIT_GRAPH``
as a compile-time gamble, R003 actively polices AGAINST 64-bit drift,
and until this tier the only machine-checked width contract was the one
``kbits + sbits <= 31`` predicate in ops/segment.py.  A silent int32
overflow in a cumsum, degree sum, or packed key at scale 28 produces
WRONG LABELS, not a crash — the worst failure class for a clustering
service.  This module closes the static half; analysis/widthaudit.py
runs the dynamic half (W001-W003) over real traced jaxprs.

**The interval model.**  A tiny forward abstract interpreter runs over
every function in the device-path modules (``ops/``, ``coarsen/``,
``louvain/``, ``kernels/``, ``core/``).  Each value carries an abstract
triple ``(bound, extent, int32)``:

* ``bound`` — a symbolic upper bound on the VALUE, as a JSON expression
  tree over the workload symbols (``nv_pad``, ``ne_pad``, ``nv_total``,
  ``kbits``, ``sbits``, ``B``, ``two_m``) — e.g. the packed sort key is
  ``(nv_pad << kbits) + nv_pad``;
* ``extent`` — a symbolic upper bound on the array LENGTH (the number
  of addends a reduction over it accumulates);
* ``int32`` — whether the value demonstrably flows through an int32
  dtype (``.astype(jnp.int32)``, ``dtype=jnp.int32``, ``jnp.int32(x)``).

**The symbol table.**  Bounds are seeded from NAMES, the repo's real
contract surface: parameters called ``nv_pad``/``nc``/``num_segments``
bound at ``nv_pad``, ``ne_pad`` at ``ne_pad``, edge-slab arrays
(``src``/``dst``/``ckey``/``w``...) get extent ``ne_pad`` and vertex-id
value bound ``nv_pad``, per-vertex arrays (``comm``/``vdeg``/``lab``...)
get extent ``nv_pad``.  Unknown names stay unknown — a bounded false
negative, never a false positive.

**Eligibility predicates refine the bounds.**  A leading
``if ne_pad > SLAB_NE_MAX: raise`` fail-loud guard (the ops/segment.py
slab contract) refines the symbol's bound for the rest of the function,
and an enclosing ``if fits32:`` / ``if packable:`` guard whose
(one-level-expanded) predicate mentions the bit-budget names marks a
packing site as TIED to its guard.  The rules:

* **R026** — int32-typed arithmetic whose symbolic upper bound exceeds
  2^31 - 1 when evaluated at the registry's declared max workload
  (:data:`MAX_WORKLOAD` — pinned against
  ``workloads/registry.max_workload()`` by tier-1), unless guarded by
  an eligibility predicate or carrying ``# graftlint:
  width-ok=<reason>`` (closed inventory, ``tools/width_audit.py
  --inventory``; the R025 precedent).
* **R027** — bit-packing sites (shift/or key construction) whose bit
  budget is not provably tied to the guard predicate gating them — the
  segment.py ``kbits + sbits <= 31`` contract generalized to EVERY
  packing site.  An unknown pack bound fails CLOSED (packs are rare,
  deliberate sites).
* **R028** — ``cumsum``/``sum``/``bincount``-class reductions over
  ``ne_pad``-extent arrays accumulating in an int32 input dtype: the
  run-id/compaction-offset class.  At ne_pad = 2^32 the cumsum of a
  mask already wraps; the SLAB_NE_MAX = 2^30 refinement (or a
  ``width-ok`` annotation) is the only way through.

Facts ride the tier-2 summary (and therefore the incremental lint
cache) under the ``"width"`` key, exactly like the lock and mesh
summaries; the dynamic W00x results are NEVER cached.
"""

from __future__ import annotations

import ast
import re

from cuvite_tpu.analysis.engine import Finding, SourceFile, dotted, register

WIDTH_SUMMARY_VERSION = 1

INT32_MAX = (1 << 31) - 1

# The registry's declared max workload, in symbols (tier-1 pins this
# dict == workloads/registry.max_workload(); the static tier itself
# stays stdlib-only so linting never imports jax/numpy):
#   nv_pad/nv_total — R-MAT scale-28 vertex space (2^28 ids, already
#     pow2 so padding is the identity; Friendster pads to 2^27);
#   ne_pad — the directed edge slab ceiling (Friendster's 3.61 B
#     directed rows and the scale-28 synth law's 16 * 2^28 both pad to
#     2^32);
#   two_m — total directed weight mass ceiling (unit weights make it
#     ne_pad; 2^33 leaves headroom for small integer weights);
#   kbits/sbits — the packed-sort bit budget at that vertex space
#     (key_bound = nv_pad -> 28 bits, src_bound = nv_pad + 1 -> 29);
#   B — the serving batch-ladder ceiling (core/batch.BATCH_SIZES).
MAX_WORKLOAD = {
    "nv_pad": 1 << 28,
    "nv_total": 1 << 28,
    "ne_pad": 1 << 32,
    "two_m": 1 << 33,
    "kbits": 28,
    "sbits": 29,
    "B": 64,
}

# Device-path modules the interpreter runs over (everything traced onto
# the chip plus the host-side plan/batch math that feeds it).  The
# serve/, obs/, comm/ and workloads/ layers hold no index arithmetic at
# slab extent.
WIDTH_PATH_PREFIXES = (
    "cuvite_tpu/ops/",
    "cuvite_tpu/coarsen/",
    "cuvite_tpu/louvain/",
    "cuvite_tpu/kernels/",
    "cuvite_tpu/core/",
)

_WIDTH_OK_RE = re.compile(r"#\s*graftlint:\s*width-ok\s*=\s*(.+?)\s*$")

# Parameter names whose VALUE is bounded by a workload symbol.
PARAM_BOUND_SYMBOLS = {
    "nv_pad": "nv_pad",
    "nv_total": "nv_total",
    "nc": "nv_pad",
    "num_segments": "nv_pad",
    "ne_pad": "ne_pad",
    "kbits": "kbits",
    "sbits": "sbits",
    "key_bound": "nv_pad",
    "src_bound": "nv_pad",
    "sentinel": "nv_pad",
    "b": "B",
}

# Array parameter names -> (value-bound symbol or None, extent symbol).
# Suffixed spellings (src_s, w_s, dst2) normalize to the base name.
ARRAY_PARAM_SYMBOLS = {
    "src": ("nv_pad", "ne_pad"),
    "dst": ("nv_pad", "ne_pad"),
    "ckey": ("nv_pad", "ne_pad"),
    "w": (None, "ne_pad"),
    "weights": (None, "ne_pad"),
    "starts": (None, "ne_pad"),
    "emit": (None, "ne_pad"),
    "comm": ("nv_pad", "nv_pad"),
    "labels": ("nv_pad", "nv_pad"),
    "lab": ("nv_pad", "nv_pad"),
    "vdeg": (None, "nv_pad"),
    "deg": (None, "nv_pad"),
    "present": (None, "nv_pad"),
    "sizes": ("nv_pad", "nv_pad"),
}

_REDUCTION_CALLS = {"cumsum", "cumulative_sum", "sum", "bincount"}
_MINMAX_CALLS = {"minimum", "min", "maximum", "max"}
_ALLOC_CALLS = {"zeros", "ones", "full", "empty"}

_SITE_PRIORITY = {"arith": 0, "reduction": 1, "pack": 2}

_DIGITS = "0123456789"


# ---------------------------------------------------------------------------
# Symbolic expressions: JSON-serializable nested lists.
#   ["n", 7]  ["s", "ne_pad"]  ["+", a, b]  ["*", a, b]  ["min", a, b]
#   ["max", a, b]  ["<<", a, k]  [">>", a, k]  ["bits", a]
# All values are assumed non-negative (ids, counts, offsets), which is
# what makes + an upper bound for | and the left operand one for -.


def _n(v) -> list:
    return ["n", int(v)]


def _s(name: str) -> list:
    return ["s", name]


def sym_eval(expr, env: dict):
    """Evaluate a bound expression at ``env``; None when any symbol is
    unknown (the bounded-false-negative answer)."""
    if expr is None:
        return None
    tag = expr[0]
    if tag == "n":
        return int(expr[1])
    if tag == "s":
        v = env.get(expr[1])
        return None if v is None else int(v)
    args = [sym_eval(a, env) for a in expr[1:]]
    if any(a is None for a in args):
        return None
    if tag == "+":
        return sum(args)
    if tag == "*":
        p = 1
        for a in args:
            p *= a
        return p
    if tag == "min":
        return min(args)
    if tag == "max":
        return max(args)
    if tag == "<<":
        return args[0] * (2 ** max(args[1], 0))
    if tag == ">>":
        return args[0] // (2 ** max(args[1], 0))
    if tag == "bits":
        return max(args[0], 1).bit_length()
    return None


def sym_symbols(expr) -> set:
    """The workload symbols an expression mentions."""
    out: set = set()
    if not isinstance(expr, list) or not expr:
        return out
    if expr[0] == "s":
        out.add(expr[1])
        return out
    for sub in expr[1:]:
        if isinstance(sub, list):
            out |= sym_symbols(sub)
    return out


def sym_render(expr) -> str:
    """Human form for findings: ``(nv_pad << kbits) + nv_pad``."""
    if expr is None:
        return "?"
    tag = expr[0]
    if tag == "n":
        return str(expr[1])
    if tag == "s":
        return str(expr[1])
    args = [sym_render(a) for a in expr[1:]]
    if tag == "bits":
        return f"bits({args[0]})"
    if tag in ("min", "max"):
        return f"{tag}({', '.join(args)})"
    return "(" + f" {tag} ".join(args) + ")"


class AVal:
    """One abstract value: (symbolic value bound, symbolic extent,
    int32-typed flag).  ``None`` bound/extent means unknown."""

    __slots__ = ("bound", "extent", "int32")

    def __init__(self, bound=None, extent=None, int32=False):
        self.bound = bound
        self.extent = extent
        self.int32 = bool(int32)


_UNKNOWN = AVal()


def _max_bound(a, b):
    if a is None or b is None:
        return None
    return ["max", a, b]


def _sum_bound(a, b):
    if a is None or b is None:
        return None
    return ["+", a, b]


def _first_extent(*vals):
    for v in vals:
        if v is not None and v.extent is not None:
            return v.extent
    return None


def _last(name: str | None) -> str:
    return name.split(".")[-1] if name else ""


def _is_int32_dtype_expr(node: ast.AST | None) -> bool:
    """Does a dtype expression demonstrably denote a 32-bit-or-narrower
    integer (jnp.int32 / np.int32 / "int32" / int16/int8 variants)?"""
    if node is None:
        return False
    name = dotted(node)
    if name is None and isinstance(node, ast.Constant) \
            and isinstance(node.value, str):
        name = node.value
    if not name:
        return False
    last = name.split(".")[-1]
    return last in ("int32", "int16", "int8", "uint32", "uint16", "uint8")


def _width_ok_lines(sf: SourceFile) -> dict:
    """{lineno: reason} for every ``# graftlint: width-ok=`` pragma
    (real comment tokens, the replicated-ok discipline)."""
    out: dict = {}
    for lineno, comment in sf._iter_comments():
        if "width-ok" not in comment:
            continue
        m = _WIDTH_OK_RE.search(comment)
        if m:
            out[lineno] = m.group(1)
    return out


def _module_int_consts(sf: SourceFile) -> dict:
    """Module-level ``NAME = <int expr>`` constants, with shift/arith
    folding (``SLAB_NE_MAX = 1 << 30``) — the raise-guard ceilings."""
    out: dict = {}
    for node in sf.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            v = _const_int(node.value, out)
            if v is not None:
                out[node.targets[0].id] = v
    return out


def _const_int(node: ast.AST, consts: dict):
    """Fold an int-constant expression (Constant / module const Name /
    +-*<< BinOp over those); None when not statically an int."""
    if isinstance(node, ast.Constant):
        return node.value if isinstance(node.value, int) \
            and not isinstance(node.value, bool) else None
    if isinstance(node, ast.Name):
        return consts.get(node.id)
    if isinstance(node, ast.BinOp):
        lo = _const_int(node.left, consts)
        hi = _const_int(node.right, consts)
        if lo is None or hi is None:
            return None
        if isinstance(node.op, ast.LShift):
            return lo << hi
        if isinstance(node.op, ast.Add):
            return lo + hi
        if isinstance(node.op, ast.Sub):
            return lo - hi
        if isinstance(node.op, ast.Mult):
            return lo * hi
        if isinstance(node.op, ast.Pow) and 0 <= hi <= 64:
            return lo ** hi
    return None


def _seed_aval(name: str) -> AVal | None:
    key = name if name in PARAM_BOUND_SYMBOLS \
        or name in ARRAY_PARAM_SYMBOLS \
        else name.split("_")[0].rstrip(_DIGITS)
    if key in PARAM_BOUND_SYMBOLS:
        return AVal(bound=_s(PARAM_BOUND_SYMBOLS[key]))
    if key in ARRAY_PARAM_SYMBOLS:
        bsym, esym = ARRAY_PARAM_SYMBOLS[key]
        return AVal(bound=_s(bsym) if bsym else None, extent=_s(esym))
    return None


def _names_in(node: ast.AST) -> set:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


# ---------------------------------------------------------------------------
# The per-function interpreter.


class _FnInterp:
    """Forward abstract interpretation of ONE function body, recording
    width hazard sites.  Statements are walked in order; ``if X: raise``
    prologue guards refine symbol bounds for the remainder; enclosing
    ``if`` predicates stack onto every recorded site."""

    def __init__(self, sf: SourceFile, info, consts: dict,
                 width_ok: dict, sites: list):
        self.sf = sf
        self.info = info
        self.consts = consts
        self.width_ok = width_ok
        self.sites = sites
        self.env: dict = {}
        self.refined: dict = {}
        self.guards: list = []
        self.assign_text: dict = {}
        self.bitlen_bases: dict = {}
        for p in info.params:
            seeded = _seed_aval(p)
            if seeded is not None:
                self.env[p] = seeded
        # Pre-pass: one-level guard expansion text and bit_length
        # derivation bases ("kbits = max(key_bound - 1, 1).bit_length()"
        # -> kbits derives from key_bound).
        for node in ast.walk(info.node):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                tgt = node.targets[0].id
                try:
                    self.assign_text[tgt] = ast.unparse(node.value)
                except Exception:
                    pass
                for sub in ast.walk(node.value):
                    if isinstance(sub, ast.Call) \
                            and isinstance(sub.func, ast.Attribute) \
                            and sub.func.attr == "bit_length":
                        self.bitlen_bases.setdefault(tgt, set()).update(
                            _names_in(sub.func.value))

    # -- recording ----------------------------------------------------

    def _record(self, node: ast.AST, kind: str, bound, *, extent=None,
                shift=(), int32=False):
        line = getattr(node, "lineno", 1)
        site = {
            "fn": self.info.name,
            "line": line,
            "snippet": self.sf.line(line),
            "kind": kind,
            "bound": bound,
            "extent": extent,
            "shift": sorted(shift),
            "guards": list(self.guards),
            "tied": self._tied(shift) if kind == "pack" else False,
            "refined": dict(self.refined),
            "width_ok": self.width_ok.get(line),
            "int32": bool(int32),
        }
        for i, prev in enumerate(self.sites):
            if prev["line"] == line and prev["fn"] == self.info.name:
                if _SITE_PRIORITY[kind] > _SITE_PRIORITY[prev["kind"]]:
                    self.sites[i] = site
                return
        self.sites.append(site)

    def _tied(self, shift_names) -> bool:
        """Is a pack's bit budget provably tied to a gating predicate?
        True when an enclosing guard (one-level expanded) mentions a
        shift-amount name, one of its ``bit_length`` base names, or any
        ``bit_length`` call — or when a prologue raise-guard already
        refined a symbol the shift amount derives from."""
        names = set(shift_names)
        for nm in list(names):
            names |= self.bitlen_bases.get(nm, set())
        texts = []
        for g in self.guards:
            texts.append(g)
            for nm in _names_in_text(g):
                if nm in self.assign_text:
                    texts.append(self.assign_text[nm])
        for t in texts:
            if "bit_length" in t:
                return True
            toks = _names_in_text(t)
            if toks & names:
                return True
        for nm in names:
            seeded = self.env.get(nm) or _seed_aval(nm)
            if seeded is not None and seeded.bound is not None:
                if sym_symbols(seeded.bound) & set(self.refined):
                    return True
        return False

    # -- statements ---------------------------------------------------

    def run(self):
        self._stmts(self.info.node.body)

    def _stmts(self, body):
        for st in body:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # nested defs get their own pass
            if isinstance(st, ast.If):
                self._if(st)
            elif isinstance(st, (ast.For, ast.While)):
                if isinstance(st, ast.For):
                    self._assign_target(st.target, self._eval(st.iter))
                else:
                    self._eval(st.test)
                self._stmts(st.body)
                self._stmts(st.orelse)
            elif isinstance(st, ast.With):
                self._stmts(st.body)
            elif isinstance(st, ast.Try):
                self._stmts(st.body)
                for h in st.handlers:
                    self._stmts(h.body)
                self._stmts(st.orelse)
                self._stmts(st.finalbody)
            elif isinstance(st, ast.Assign):
                val = self._eval(st.value)
                for t in st.targets:
                    self._assign_target(t, val, value_node=st.value)
            elif isinstance(st, ast.AnnAssign) and st.value is not None:
                self._assign_target(st.target, self._eval(st.value))
            elif isinstance(st, ast.AugAssign):
                self._eval(st.value)
                if isinstance(st.target, ast.Name):
                    self.env[st.target.id] = _UNKNOWN
            elif isinstance(st, (ast.Expr, ast.Return)):
                if getattr(st, "value", None) is not None:
                    self._eval(st.value)
            elif isinstance(st, ast.Assert):
                self._eval(st.test)

    def _if(self, st: ast.If):
        # Prologue fail-loud guard: ``if SYM > CEIL: raise`` refines the
        # symbol's bound for everything after it (the SLAB_NE_MAX
        # eligibility-predicate shape).
        if len(st.body) == 1 and isinstance(st.body[0], ast.Raise) \
                and not st.orelse and self._refine_from(st.test):
            return
        try:
            gtext = ast.unparse(st.test)
        except Exception:
            gtext = "<guard>"
        self._eval(st.test)
        self.guards.append(gtext)
        self._stmts(st.body)
        self.guards.pop()
        self._stmts(st.orelse)

    def _refine_from(self, test: ast.AST) -> bool:
        if not (isinstance(test, ast.Compare) and len(test.ops) == 1
                and isinstance(test.ops[0], (ast.Gt, ast.GtE))
                and isinstance(test.left, ast.Name)):
            return False
        ceil = _const_int(test.comparators[0], self.consts)
        if ceil is None:
            return False
        if isinstance(test.ops[0], ast.GtE):
            ceil -= 1
        name = test.left.id
        aval = self.env.get(name) or _seed_aval(name)
        sym = None
        if aval is not None and aval.bound is not None \
                and aval.bound[0] == "s":
            sym = aval.bound[1]
        elif name in MAX_WORKLOAD:
            sym = name
        if sym is None:
            return False
        prev = self.refined.get(sym)
        self.refined[sym] = ceil if prev is None else min(prev, ceil)
        return True

    def _assign_target(self, target, val: AVal, value_node=None):
        if isinstance(target, ast.Name):
            if (val is _UNKNOWN or (val.bound is None
                                    and val.extent is None)):
                # Unknown RHS into a contract-named local adopts the
                # symbol (``nv_pad = acc.shape[0]`` keeps its meaning).
                seeded = _seed_aval(target.id)
                if seeded is not None and target.id in PARAM_BOUND_SYMBOLS:
                    self.env[target.id] = seeded
                    return
            self.env[target.id] = val
        elif isinstance(target, (ast.Tuple, ast.List)):
            parts = None
            if isinstance(value_node, (ast.Tuple, ast.List)) \
                    and len(value_node.elts) == len(target.elts):
                parts = [self._eval(e) for e in value_node.elts]
            for i, t in enumerate(target.elts):
                if isinstance(t, ast.Name):
                    self.env[t.id] = parts[i] if parts is not None \
                        else AVal(extent=val.extent)

    # -- expressions --------------------------------------------------

    def _eval(self, node: ast.AST) -> AVal:
        if node is None:
            return _UNKNOWN
        if isinstance(node, ast.Constant):
            if isinstance(node.value, bool):
                return AVal(bound=_n(1))
            if isinstance(node.value, int):
                return AVal(bound=_n(abs(node.value)))
            return _UNKNOWN
        if isinstance(node, ast.Name):
            got = self.env.get(node.id)
            if got is not None:
                return got
            if node.id in self.consts:
                return AVal(bound=_n(self.consts[node.id]))
            seeded = _seed_aval(node.id)
            return seeded if seeded is not None else _UNKNOWN
        if isinstance(node, ast.BinOp):
            return self._binop(node)
        if isinstance(node, ast.BoolOp):
            for v in node.values:
                self._eval(v)
            return AVal(bound=_n(1),
                        extent=_first_extent(*[self._eval(v)
                                               for v in node.values]))
        if isinstance(node, ast.Compare):
            left = self._eval(node.left)
            rights = [self._eval(c) for c in node.comparators]
            return AVal(bound=_n(1),
                        extent=_first_extent(left, *rights))
        if isinstance(node, ast.UnaryOp):
            inner = self._eval(node.operand)
            if isinstance(node.op, ast.Invert):
                return AVal(bound=_n(1) if inner.bound == _n(1) else None,
                            extent=inner.extent, int32=inner.int32)
            return AVal(bound=inner.bound, extent=inner.extent,
                        int32=inner.int32)
        if isinstance(node, ast.IfExp):
            self._eval(node.test)
            a, b = self._eval(node.body), self._eval(node.orelse)
            return AVal(bound=_max_bound(a.bound, b.bound),
                        extent=_first_extent(a, b),
                        int32=a.int32 or b.int32)
        if isinstance(node, ast.Call):
            return self._call(node)
        if isinstance(node, ast.Subscript):
            return self._subscript(node)
        if isinstance(node, ast.Attribute):
            base = self._eval(node.value)
            if node.attr in ("T", "real", "imag"):
                return base
            return _UNKNOWN
        if isinstance(node, (ast.Tuple, ast.List)):
            for e in node.elts:
                self._eval(e)
            return _UNKNOWN
        if isinstance(node, ast.Starred):
            return self._eval(node.value)
        return _UNKNOWN

    def _binop(self, node: ast.BinOp) -> AVal:
        a = self._eval(node.left)
        b = self._eval(node.right)
        int32 = a.int32 or b.int32
        extent = _first_extent(a, b)
        op = node.op
        bound = None
        shift_names: set = set()
        kind = "arith"
        if isinstance(op, ast.Add):
            bound = _sum_bound(a.bound, b.bound)
        elif isinstance(op, ast.Sub):
            c = _const_int(node.right, self.consts)
            if c is not None and a.bound is not None:
                bound = ["+", a.bound, _n(-c)]
            else:
                bound = a.bound
        elif isinstance(op, ast.Mult):
            bound = None if a.bound is None or b.bound is None \
                else ["*", a.bound, b.bound]
        elif isinstance(op, (ast.FloorDiv, ast.Div, ast.Mod)):
            bound = a.bound
        elif isinstance(op, ast.LShift):
            # A bare shift is NOT a pack: the `1 << bit_length()` pow2
            # padding idiom (next_pow2, pow2_floor, tree-sum padding,
            # mesh-size caps) shifts a constant 1, and shift-based
            # scaling never re-enters a packed field on its own.  Only a
            # BitOr that COMBINES a shifted field (below) records a pack
            # site; an int32 bare shift still falls through to the
            # generic arith record so R026 sees genuine overflow.
            if a.bound is not None and b.bound is not None:
                bound = ["<<", a.bound, b.bound]
        elif isinstance(op, ast.BitOr):
            bound = _sum_bound(a.bound, b.bound)  # a|b <= a+b, a,b >= 0
            for side in (node.left, node.right):
                for sub in ast.walk(side):
                    if isinstance(sub, ast.BinOp) \
                            and isinstance(sub.op, ast.LShift):
                        shift_names |= _names_in(sub.right)
                        kind = "pack"
        elif isinstance(op, ast.BitAnd):
            if a.bound is not None and b.bound is not None:
                bound = ["min", a.bound, b.bound]
            else:
                bound = a.bound if a.bound is not None else b.bound
        elif isinstance(op, ast.RShift):
            # `idx >> kbits` strips the low field off a flat key: the
            # bound genuinely shrinks, and keeping it symbolic lets the
            # nv_pad*nv_pad >> kbits domain cancel at evaluation.
            if a.bound is not None and b.bound is not None:
                bound = [">>", a.bound, b.bound]
            else:
                bound = a.bound
        out = AVal(bound=bound, extent=extent, int32=int32)
        if kind == "pack":
            self._record(node, "pack", bound, extent=extent,
                         shift=shift_names, int32=int32)
        elif int32 and bound is not None and sym_symbols(bound):
            self._record(node, "arith", bound, extent=extent, int32=True)
        return out

    def _subscript(self, node: ast.Subscript) -> AVal:
        # X.shape[i] -> the extent of X as a VALUE bound.
        if isinstance(node.value, ast.Attribute) \
                and node.value.attr == "shape":
            base = self._eval(node.value.value)
            return AVal(bound=base.extent)
        base = self._eval(node.value)
        self._eval(node.slice)
        return AVal(bound=base.bound, extent=base.extent, int32=base.int32)

    def _call(self, node: ast.Call) -> AVal:
        name = dotted(node.func)
        last = _last(name)
        kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}

        # Method-style receivers: x.astype(d), x.sum(), x.reshape(...),
        # x.bit_length(), x.at[i].set(v)
        if isinstance(node.func, ast.Attribute):
            attr = node.func.attr
            recv_node = node.func.value
            if attr == "astype":
                recv = self._eval(recv_node)
                is32 = node.args and _is_int32_dtype_expr(node.args[0])
                out = AVal(bound=recv.bound, extent=recv.extent,
                           int32=bool(is32) or recv.int32)
                if is32 and recv.bound is not None \
                        and sym_symbols(recv.bound):
                    self._record(node, "arith", recv.bound,
                                 extent=recv.extent, int32=True)
                return out
            if attr == "bit_length":
                recv = self._eval(recv_node)
                bound = None if recv.bound is None else ["bits", recv.bound]
                return AVal(bound=bound)
            if attr in ("reshape", "ravel", "flatten", "copy", "clip"):
                recv = self._eval(recv_node)
                for a in node.args:
                    self._eval(a)
                return AVal(bound=recv.bound, extent=recv.extent,
                            int32=recv.int32)
            if attr in _REDUCTION_CALLS and not name:
                recv = self._eval(recv_node)
                return self._reduction(node, attr, recv, kwargs)
            if attr in ("set", "add", "max", "min", "mul") \
                    and isinstance(recv_node, ast.Subscript) \
                    and isinstance(recv_node.value, ast.Attribute) \
                    and recv_node.value.attr == "at":
                base = self._eval(recv_node.value.value)
                self._eval(recv_node.slice)
                vals = [self._eval(a) for a in node.args]
                vb = vals[0].bound if vals else None
                return AVal(bound=_max_bound(base.bound, vb)
                            if vb is not None else base.bound,
                            extent=base.extent, int32=base.int32)

        args = [self._eval(a) for a in node.args]
        for v in kwargs.values():
            self._eval(v)

        if last in _REDUCTION_CALLS and args:
            return self._reduction(node, last, args[0], kwargs)
        if last in ("int", "abs", "round"):
            return args[0] if args else _UNKNOWN
        if last in ("int32", "uint32", "int16", "int8"):
            out = AVal(bound=args[0].bound if args else None,
                       extent=args[0].extent if args else None,
                       int32=True)
            if out.bound is not None and sym_symbols(out.bound):
                self._record(node, "arith", out.bound,
                             extent=out.extent, int32=True)
            return out
        if last in _MINMAX_CALLS and args:
            bounds = [a.bound for a in args]
            if any(b is None for b in bounds):
                merged = None if last in ("max", "maximum") else \
                    next((b for b in bounds if b is not None), None)
            else:
                tag = "min" if last in ("min", "minimum") else "max"
                merged = [tag] + bounds if len(bounds) > 1 else bounds[0]
            return AVal(bound=merged, extent=_first_extent(*args),
                        int32=any(a.int32 for a in args))
        if last == "arange":
            bound = args[0].bound if args else None
            is32 = _is_int32_dtype_expr(kwargs.get("dtype")) or (
                len(node.args) > 1
                and _is_int32_dtype_expr(node.args[1]))
            out = AVal(bound=bound, extent=bound, int32=is32)
            if is32 and bound is not None and sym_symbols(bound):
                self._record(node, "arith", bound, extent=bound,
                             int32=True)
            return out
        if last in _ALLOC_CALLS:
            extent = self._shape_extent(node.args[0]) if node.args \
                else None
            is32 = any(_is_int32_dtype_expr(a) for a in node.args[1:]) \
                or _is_int32_dtype_expr(kwargs.get("dtype"))
            fill = args[1].bound if last == "full" and len(args) > 1 \
                else _n(1 if last == "ones" else 0)
            return AVal(bound=fill, extent=extent, int32=is32)
        if last == "where" and len(args) >= 3:
            return AVal(bound=_max_bound(args[1].bound, args[2].bound),
                        extent=_first_extent(*args),
                        int32=args[1].int32 or args[2].int32)
        if last in ("take", "take_along_axis") and args:
            return AVal(bound=args[0].bound,
                        extent=args[1].extent if len(args) > 1
                        else args[0].extent,
                        int32=args[0].int32)
        if last == "concatenate":
            return AVal(extent=_first_extent(*args))
        if last == "broadcasted_iota":
            is32 = node.args and _is_int32_dtype_expr(node.args[0])
            return AVal(int32=bool(is32))
        # Unknown call: propagate the widest argument extent (the sorted
        # copies / run masks keep their slab extent through helpers).
        return AVal(extent=_first_extent(*args))

    def _shape_extent(self, shape_node: ast.AST):
        if isinstance(shape_node, (ast.Tuple, ast.List)):
            bounds = []
            for e in shape_node.elts:
                b = self._eval(e).bound
                if b is None:
                    return None
                bounds.append(b)
            if not bounds:
                return None
            out = bounds[0]
            for b in bounds[1:]:
                out = ["*", out, b]
            return out
        return self._eval(shape_node).bound

    def _reduction(self, node: ast.Call, op: str, inp: AVal,
                   kwargs: dict) -> AVal:
        is32 = inp.int32 or _is_int32_dtype_expr(kwargs.get("dtype"))
        if op == "bincount":
            # counts are bounded by the number of addends
            bound = inp.extent
            extent = None
            ml = kwargs.get("minlength")
            if ml is not None:
                extent = self._eval(ml).bound
            if kwargs.get("weights") is not None:
                is32 = False  # weighted bincount accumulates the weights
        else:
            per = inp.bound if inp.bound is not None else _n(1)
            bound = None if inp.extent is None else ["*", inp.extent, per]
            extent = inp.extent if op in ("cumsum", "cumulative_sum") \
                else None
        out = AVal(bound=bound, extent=extent, int32=is32)
        if is32 and bound is not None and sym_symbols(bound):
            self._record(node, "reduction", bound, extent=inp.extent,
                         int32=True)
        return out


def _names_in_text(text: str) -> set:
    return set(re.findall(r"[A-Za-z_][A-Za-z0-9_]*", text))


# ---------------------------------------------------------------------------
# Summary + inventory.


def width_summary(sf: SourceFile) -> dict:
    """The JSON-serializable width facts of one file; rides the tier-2
    summary under the ``"width"`` key.  Non-device-path files carry an
    empty site list (the serve/obs/comm layers hold no slab-extent
    index arithmetic)."""
    if not sf.rel.startswith(WIDTH_PATH_PREFIXES):
        return {"version": WIDTH_SUMMARY_VERSION, "sites": []}
    consts = _module_int_consts(sf)
    width_ok = _width_ok_lines(sf)
    sites: list = []
    for info in sf.functions:
        try:
            _FnInterp(sf, info, consts, width_ok, sites).run()
        except RecursionError:
            continue
    sites.sort(key=lambda s: (s["line"], s["fn"]))
    return {"version": WIDTH_SUMMARY_VERSION, "sites": sites}


def width_inventory(summaries) -> list:
    """Every ``width-ok``-annotated site in the summary set:
    [{rel, line, fn, kind, bound, reason, snippet}] — the closed,
    justified inventory of deliberate 32-bit choices
    (``python tools/width_audit.py --inventory`` prints it)."""
    out = []
    for s in summaries:
        width = (s or {}).get("width") or {}
        for site in width.get("sites", ()):
            if site.get("width_ok"):
                out.append({
                    "rel": s["rel"], "line": site["line"],
                    "fn": site["fn"], "kind": site["kind"],
                    "bound": sym_render(site["bound"]),
                    "reason": site["width_ok"],
                    "snippet": site["snippet"],
                })
    return sorted(out, key=lambda d: (d["rel"], d["line"]))


# ---------------------------------------------------------------------------
# Rules.

from cuvite_tpu.analysis.callgraph import ProjectRule  # noqa: E402


def _site_env(site: dict) -> dict:
    env = dict(MAX_WORKLOAD)
    env.update(site.get("refined") or {})
    return env


def _guarded(site: dict) -> bool:
    """Is the site inside a predicate that mentions one of the symbols
    its bound depends on (an eligibility guard)?"""
    syms = sym_symbols(site["bound"]) | sym_symbols(site.get("extent"))
    if not syms:
        return False
    for g in site.get("guards", ()):
        if _names_in_text(g) & syms:
            return True
    return False


def _wfind(rule, summary, site, message) -> Finding:
    return Finding(rule=rule.id, severity=rule.severity,
                   path=summary["rel"], line=site["line"],
                   message=message, snippet=site["snippet"])


def _width_sites(project):
    for summary in project.summaries:
        width = summary.get("width") or {}
        for site in width.get("sites", ()):
            if site.get("width_ok"):
                continue
            yield summary, site


@register
class Int32BoundOverflow(ProjectRule):
    id = "R026"
    severity = "high"
    title = "int32 index arithmetic whose symbolic bound exceeds " \
            "2^31-1 at the declared max workload"

    def check_project(self, project):
        for summary, site in _width_sites(project):
            if site["kind"] == "pack" or not site.get("int32"):
                continue
            if site["kind"] == "reduction" \
                    and "ne_pad" in sym_symbols(site.get("extent")):
                continue  # R028's partition
            val = sym_eval(site["bound"], _site_env(site))
            if val is None or val <= INT32_MAX:
                continue
            if _guarded(site):
                continue
            yield _wfind(
                self, summary, site,
                f"int32-typed value in '{site['fn']}' is bounded by "
                f"{sym_render(site['bound'])} = {val} at the registry's "
                f"declared max workload (> 2^31-1 = {INT32_MAX}): a "
                "silent wraparound here produces wrong labels, not a "
                "crash.  Guard it with an eligibility predicate (the "
                "SLAB_NE_MAX raise-guard shape), widen the dtype, or "
                "justify with '# graftlint: width-ok=<reason>' on this "
                "line (the annotation feeds the closed width inventory, "
                "tools/width_audit.py --inventory)")


@register
class UntiedBitPack(ProjectRule):
    id = "R027"
    severity = "high"
    title = "bit-packing site whose bit budget is not provably tied " \
            "to the guard predicate gating it"

    def check_project(self, project):
        for summary, site in _width_sites(project):
            if site["kind"] != "pack" or site.get("tied"):
                continue
            val = sym_eval(site["bound"], _site_env(site))
            if val is not None and val <= INT32_MAX:
                continue  # provably fits even unguarded
            shown = sym_render(site["bound"])
            at = "unknown" if val is None else str(val)
            yield _wfind(
                self, summary, site,
                f"packed key in '{site['fn']}' (budget "
                f"{shown}, {at} at max workload) is not tied to any "
                "gating predicate: nothing proves the shifted field "
                "cannot bleed into (or past) the sign bit — the "
                "segment.py contract is 'pack ONLY under a predicate "
                "that bounds the bit budget' (kbits + sbits <= 31).  "
                "Gate it on the packing bit width, bound the id space "
                "with a fail-loud raise-guard, or justify with "
                "'# graftlint: width-ok=<reason>'")


@register
class Int32SlabReduction(ProjectRule):
    id = "R028"
    severity = "high"
    title = "cumsum/sum/bincount over an ne_pad-extent array " \
            "accumulating in int32"

    def check_project(self, project):
        for summary, site in _width_sites(project):
            if site["kind"] != "reduction" or not site.get("int32"):
                continue
            if "ne_pad" not in sym_symbols(site.get("extent")):
                continue
            val = sym_eval(site["bound"], _site_env(site))
            if val is None or val <= INT32_MAX:
                continue
            if _guarded(site):
                continue
            yield _wfind(
                self, summary, site,
                f"int32 reduction in '{site['fn']}' accumulates over an "
                f"edge-slab extent ({sym_render(site.get('extent'))}); "
                f"its bound {sym_render(site['bound'])} = {val} clears "
                f"2^31-1 at the declared max workload.  The run-id/"
                "compaction-offset class: at a 2^32-row slab the cumsum "
                "of a MASK already wraps.  Bound the slab with the "
                "SLAB_NE_MAX raise-guard (ops/segment.py), accumulate "
                "wider, or justify with '# graftlint: "
                "width-ok=<reason>'")
