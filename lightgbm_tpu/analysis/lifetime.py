"""Rule pack: buffer-lifetime ("lifelint", donation half).

The pipelined loop donates its double-buffered planar state into every
iteration dispatch (`donate_argnums` on the compile-manager entries)
and lets readbacks trail their dispatch by a whole pipeline step
(`copy_to_host_async` handles resolved one period later). Both are
invisible on the CPU tier-1 suite — donation is a no-op there and an
undrained handle just resolves late — and both corrupt silently on
real TPU HBM: a read of a donated buffer observes whatever the aliased
output wrote, and a handle outliving its source fetches freed memory.

What is checked
---------------
1. **use-after-donate** — a binding passed in a donated position of a
   donating callable is DEAD after the call statement; any later read
   of it in the same function without an intervening rebind is a
   finding. The canonical safe shape rebinds in the same statement:
   `state = entry(state, ...)`.
2. **donate-escape-closure** — a binding that is donated anywhere in a
   function must not be captured by a nested function/lambda defined
   in that function: the closure typically runs later (warmup thread,
   callback) against a buffer that no longer exists.
3. **escape-checkpoint / escape-flight / escape-telemetry** — device
   values (per the sync_points device-taint heuristic) must not be
   stored into checkpoint state (`checkpoint_state` methods — the PR 8
   `_drain_stop_check` discipline, generalized: robust/checkpoint.py
   payloads must be device-ref-free), flight-recorder dump payloads,
   or telemetry gauges/counters. Launder through `np.asarray`, `jax.
   device_get`, `int`/`float`/`bool` first.
4. **fetch-no-drain / fetch-ckpt-live** — a class that parks
   `copy_to_host_async` handles on an instance attribute must own a
   drain (some method resets the attribute), and its
   `checkpoint_state` must reach that drain: a checkpoint must never
   carry live device refs.

Donating callables are discovered statically: attributes/locals bound
from `jax.jit(..., donate_argnums=...)`, `*.shared_entry(...,
donate_argnums=...)` or `*.jit_entry(..., donate_argnums=...)`
(compile/manager.py), looked through `instrument_kernel(...)` wrappers
and through methods that merely forward a parameter into a donated
position (`train_iter_persistent` donates its `data` argument).

Suppress with `# tpulint: donate-ok(<reason>)` on the offending line
or the line above. Analysis is function-local and source-order (no
back-edge tracking through loops): over-approximation is a pragma
away from quiet, an unflagged use-after-donate is silent corruption.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Set, Tuple

from .core import Finding, FunctionInfo, Package, dotted
from .sync_points import _DeviceTaint

RULE = "buffer-lifetime"

# factory callables whose result donates (positions from the literal
# donate_argnums keyword)
_ENTRY_FACTORIES = ("shared_entry", "jit_entry")
# wrappers that preserve donation semantics of their first argument
_TRANSPARENT_WRAPPERS = ("instrument_kernel",)

# conversions that launder a device value into host data
_LAUNDER_CALLS = {"asarray", "array", "device_get", "int", "float",
                  "bool", "str", "len", "list", "tuple", "dict"}

# methods whose return payload must stay device-ref-free
_CKPT_METHOD_NAMES = ("checkpoint_state",)

# attribute-call receivers treated as a flight-recorder dump
_FLIGHT_DUMP_ATTR = "dump"
# telemetry publication calls (second positional arg is the payload)
_TELEMETRY_CALLS = ("set_gauge", "inc", "observe", "add_time",
                    "observe_latency")


def _donate_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
    """Literal donate_argnums of a Call, or None when absent/dynamic."""
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return (v.value,)
        if isinstance(v, (ast.Tuple, ast.List)):
            out = []
            for e in v.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    out.append(e.value)
                else:
                    return None
            return tuple(out)
        return None
    return None


@dataclasses.dataclass
class DonationSite:
    """One statically-discovered donating registration."""
    rel: str
    line: int
    func: str                 # enclosing function qual
    entry_name: str           # literal entry name ("" for bare jax.jit)
    positions: Tuple[int, ...]


class _ModuleDonations:
    """Donating bindings of one module: class attrs, locals, and
    wrapper functions, each mapped to donated positional indices."""

    def __init__(self, pkg: Package, rel: str) -> None:
        self.pkg = pkg
        self.rel = rel
        # (cls or "", attr/local name) -> donated positions
        self.attrs: Dict[Tuple[str, str], Tuple[int, ...]] = {}
        # qual -> positions, for functions RETURNING a donating callable
        self.wrappers_returning: Dict[str, Tuple[int, ...]] = {}
        self.sites: List[DonationSite] = []

    # -- classification of value expressions ----------------------------
    def _expr_positions(self, node: ast.AST, fi: FunctionInfo,
                        local: Dict[str, Tuple[int, ...]],
                        record_site: bool = False
                        ) -> Optional[Tuple[int, ...]]:
        """Donated positions of the callable this expression evaluates
        to, or None when it is not a donating callable."""
        if isinstance(node, ast.Name):
            return local.get(node.id)
        a = _self_attr(node)
        if a is not None:
            return self.attrs.get((fi.cls or "", a))
        if not isinstance(node, ast.Call):
            return None
        fd = dotted(node.func)
        leaf = fd.split(".")[-1] if fd else ""
        if not leaf and isinstance(node.func, ast.Attribute):
            # non-Name receiver chain: `get_manager().shared_entry(...)`
            leaf = node.func.attr
        if leaf == "jit":
            pos = _donate_positions(node)
            if pos:
                if record_site:
                    self.sites.append(DonationSite(
                        self.rel, node.lineno, fi.qual, "", pos))
                return pos
            return None
        if leaf in _ENTRY_FACTORIES:
            pos = _donate_positions(node)
            if pos:
                name = ""
                if node.args and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str):
                    name = node.args[0].value
                if record_site:
                    self.sites.append(DonationSite(
                        self.rel, node.lineno, fi.qual, name, pos))
                return pos
            return None
        if leaf in _TRANSPARENT_WRAPPERS and node.args:
            return self._expr_positions(node.args[0], fi, local,
                                        record_site)
        # self-method call returning a donating callable
        # (a `self._build_entry(k)` that returns its registered entry)
        callees = self.pkg.resolve_call(self.rel, fi, node.func,
                                        fallback=False)
        for q in callees:
            if q in self.wrappers_returning:
                return self.wrappers_returning[q]
        return None

    def collect(self) -> None:
        # two passes: pass 1 binds direct registrations, pass 2 looks
        # through instrument_kernel / returning-method indirection
        for _ in range(2):
            for qual, fi in self.pkg.functions.items():
                if fi.rel != self.rel:
                    continue
                assigns, returns, _ = _fn_index(fi)
                local: Dict[str, Tuple[int, ...]] = {}
                for stmt in assigns:
                    pos = self._expr_positions(stmt.value, fi, local,
                                               record_site=False)
                    if pos is None:
                        continue
                    for t in stmt.targets:
                        tgt = t.value if isinstance(t, ast.Subscript) \
                            else t
                        a = _self_attr(tgt)
                        if a is not None:
                            self.attrs[(fi.cls or "", a)] = pos
                        elif isinstance(tgt, ast.Name):
                            local[tgt.id] = pos
                for stmt in returns:
                    if stmt.value is None:
                        continue
                    pos = self._expr_positions(stmt.value, fi, local)
                    if pos is not None:
                        self.wrappers_returning[qual] = pos
        # record inventory sites once (third pass, sites deduped by line)
        for qual, fi in self.pkg.functions.items():
            if fi.rel != self.rel:
                continue
            assigns, returns, _ = _fn_index(fi)
            local2: Dict[str, Tuple[int, ...]] = {}
            for stmt in assigns:
                self._expr_positions(stmt.value, fi, local2,
                                     record_site=True)
            for stmt in returns:
                if stmt.value is not None:
                    self._expr_positions(stmt.value, fi, local2,
                                         record_site=True)


def _fn_index(fi: FunctionInfo
              ) -> Tuple[List[ast.Assign], List[ast.Return],
                         List[ast.Call]]:
    """Assign/Return/Call nodes of one function, walked once and
    memoized on the FunctionInfo — the donation model visits every
    function ~6 times (collect passes, wrapper fixpoint, rule scans)
    and re-walking dominates the pack's runtime."""
    idx = getattr(fi, "_life_index", None)
    if idx is None:
        assigns: List[ast.Assign] = []
        returns: List[ast.Return] = []
        calls: List[ast.Call] = []
        for n in ast.walk(fi.node):
            if isinstance(n, ast.Assign):
                assigns.append(n)
            elif isinstance(n, ast.Return):
                returns.append(n)
            elif isinstance(n, ast.Call):
                calls.append(n)
        idx = (assigns, returns, calls)
        fi._life_index = idx
    return idx


def _self_attr(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) \
            and node.value.id == "self":
        return node.attr
    return None


def _binding(node: ast.AST) -> Optional[Tuple[str, str]]:
    """Trackable donated binding: ("name", x) or ("attr", x)."""
    if isinstance(node, ast.Name):
        return ("name", node.id)
    a = _self_attr(node)
    if a is not None:
        return ("attr", a)
    return None


class _Donations:
    """Package-wide donation model."""

    def __init__(self, pkg: Package) -> None:
        self.pkg = pkg
        self.modules: Dict[str, _ModuleDonations] = {}
        # per-function memos: the donating-locals map and the literal
        # tuple map depend only on module-level donation state, which
        # is fixed after collect() — recomputing them per call site
        # turns the pack quadratic on large modules
        self._locals_cache: Dict[str, Dict[str, Tuple[int, ...]]] = {}
        self._tuples_cache: Dict[str, Dict[str, List[ast.AST]]] = {}
        for rel in pkg.files:
            md = _ModuleDonations(pkg, rel)
            md.collect()
            self.modules[rel] = md
        # wrapper methods that forward a param into a donated position:
        # qual -> donated call positions (bound-method view, self
        # stripped). Iterate to a small fixpoint so wrappers of
        # wrappers resolve (depth 2 covers the package).
        self.method_wrappers: Dict[str, Tuple[int, ...]] = {}
        for _ in range(2):
            for qual, fi in pkg.functions.items():
                pos = self._wrapper_positions(fi)
                if pos:
                    self.method_wrappers[qual] = pos

    # -- donating call detection ----------------------------------------
    def call_positions(self, fi: FunctionInfo, call: ast.Call,
                       local_tuples: Dict[str, List[ast.AST]]
                       ) -> Optional[Tuple[int, ...]]:
        """Donated positions of one call expression, or None."""
        md = self.modules[fi.rel]
        f = call.func
        if isinstance(f, ast.Subscript):
            f = f.value
        if isinstance(f, ast.Name):
            # locals are per-collect-pass; re-derive cheaply
            pos = self._local_positions(fi, f.id)
            if pos is not None:
                return pos
        a = _self_attr(f)
        if a is not None:
            pos = md.attrs.get((fi.cls or "", a))
            if pos is not None:
                return pos
        # method call on another object: confident resolution first,
        # unique simple-name fallback second (a taint analysis must not
        # let `x.update(...)` hit every `update` in the package)
        callees = self.pkg.resolve_call(fi.rel, fi, call.func,
                                        fallback=False)
        if not callees and isinstance(call.func, ast.Attribute):
            cands = self.pkg.by_name.get(call.func.attr, [])
            if len(cands) == 1:
                callees = set(cands)
        for q in callees:
            if q in self.method_wrappers:
                return self.method_wrappers[q]
        return None

    def _local_positions(self, fi: FunctionInfo, name: str
                         ) -> Optional[Tuple[int, ...]]:
        local = self._locals_cache.get(fi.qual)
        if local is None:
            md = self.modules[fi.rel]
            local = {}
            for stmt in _fn_index(fi)[0]:
                pos = md._expr_positions(stmt.value, fi, local)
                if pos is None:
                    continue
                for t in stmt.targets:
                    tgt = t.value if isinstance(t, ast.Subscript) \
                        else t
                    if isinstance(tgt, ast.Name):
                        local[tgt.id] = pos
            self._locals_cache[fi.qual] = local
        return local.get(name)

    def local_tuples(self, fi: FunctionInfo) -> Dict[str, List[ast.AST]]:
        tuples = self._tuples_cache.get(fi.qual)
        if tuples is None:
            tuples = _local_tuples(fi.node)
            self._tuples_cache[fi.qual] = tuples
        return tuples

    def donated_args(self, fi: FunctionInfo, call: ast.Call,
                     positions: Tuple[int, ...],
                     local_tuples: Dict[str, List[ast.AST]]
                     ) -> List[ast.AST]:
        """Argument expressions occupying the donated positions,
        expanding one level of `*args` where args is a local tuple."""
        flat: List[ast.AST] = []
        for a in call.args:
            if isinstance(a, ast.Starred) and isinstance(a.value, ast.Name) \
                    and a.value.id in local_tuples:
                flat.extend(local_tuples[a.value.id])
            else:
                flat.append(a)
        return [flat[p] for p in positions if p < len(flat)]

    def _wrapper_positions(self, fi: FunctionInfo
                           ) -> Optional[Tuple[int, ...]]:
        """Call positions (self stripped) of params this function
        forwards into a donated position of a donating call."""
        params = fi.params
        offset = 1 if params and params[0] == "self" else 0
        tuples = self.local_tuples(fi)
        donated: Set[int] = set()
        for node in _fn_index(fi)[2]:
            pos = self.call_positions(fi, node, tuples)
            if pos is None:
                continue
            for arg in self.donated_args(fi, node, pos, tuples):
                if isinstance(arg, ast.Name) and arg.id in params:
                    donated.add(params.index(arg.id) - offset)
        return tuple(sorted(p for p in donated if p >= 0)) or None

    def inventory(self) -> List[DonationSite]:
        out: List[DonationSite] = []
        seen: Set[Tuple[str, int]] = set()
        for md in self.modules.values():
            for s in md.sites:
                if (s.rel, s.line) in seen:
                    continue
                seen.add((s.rel, s.line))
                out.append(s)
        return sorted(out, key=lambda s: (s.rel, s.line))


def _local_tuples(fn_node: ast.AST) -> Dict[str, List[ast.AST]]:
    """name -> element exprs for locals assigned a tuple literal."""
    out: Dict[str, List[ast.AST]] = {}
    for stmt in ast.walk(fn_node):
        if isinstance(stmt, ast.Assign) and \
                isinstance(stmt.value, ast.Tuple):
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    out[t.id] = list(stmt.value.elts)
    return out


def donation_inventory(pkg: Package) -> List[DonationSite]:
    """Every donating registration site (entry name + positions). The
    runtime shadow-check asserts the live compile manager's donating
    entries are a subset of this inventory."""
    return _Donations(pkg).inventory()


# -- rule 1+2: use-after-donate and closure escape ------------------------

def _statements_in_order(fn_node: ast.AST) -> List[ast.stmt]:
    """Every statement in the function, OWN body only (nested function
    bodies excluded), in source order."""
    out: List[ast.stmt] = []

    def walk_body(body: List[ast.stmt]) -> None:
        for stmt in body:
            out.append(stmt)
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for field in ("body", "orelse", "finalbody"):
                sub = getattr(stmt, field, None)
                if sub:
                    walk_body(sub)
            for h in getattr(stmt, "handlers", ()):
                walk_body(h.body)

    walk_body(getattr(fn_node, "body", []))
    return sorted(out, key=lambda s: s.lineno)


def _reads_of(stmt: ast.stmt, binding: Tuple[str, str],
              skip_nested: bool = True) -> List[int]:
    kind, name = binding
    lines: List[int] = []

    class V(ast.NodeVisitor):
        def visit_Name(self, node: ast.Name) -> None:
            if kind == "name" and node.id == name \
                    and isinstance(node.ctx, ast.Load):
                lines.append(node.lineno)

        def visit_Attribute(self, node: ast.Attribute) -> None:
            if kind == "attr" and _self_attr(node) == name \
                    and isinstance(node.ctx, ast.Load):
                lines.append(node.lineno)
            self.generic_visit(node)

        def visit_FunctionDef(self, node):
            if not skip_nested:
                body = node.body if isinstance(node.body, list) \
                    else [node.body]       # Lambda body is an expr
                for s in body:
                    self.visit(s)

        visit_AsyncFunctionDef = visit_FunctionDef
        visit_Lambda = visit_FunctionDef

    V().visit(stmt)
    return lines


def _rebinds(stmt: ast.stmt, binding: Tuple[str, str]) -> bool:
    kind, name = binding
    targets: List[ast.AST] = []
    if isinstance(stmt, ast.Assign):
        targets = stmt.targets
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign, ast.For)):
        targets = [stmt.target]
    elif isinstance(stmt, ast.With):
        targets = [i.optional_vars for i in stmt.items
                   if i.optional_vars is not None]
    flat: List[ast.AST] = []
    for t in targets:
        flat.extend(t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t])
    for t in flat:
        if kind == "name" and isinstance(t, ast.Name) and t.id == name:
            return True
        if kind == "attr" and _self_attr(t) == name:
            return True
    return False


def _check_function_donations(pkg: Package, don: _Donations,
                              fi: FunctionInfo,
                              findings: List[Finding]) -> None:
    sf = pkg.files[fi.rel]
    tuples = don.local_tuples(fi)
    stmts = _statements_in_order(fi.node)
    # (binding, donation stmt) pairs in source order. Compound
    # statements are skipped: their leaf statements are in `stmts`
    # individually, so the donating call anchors at its own statement.
    donations: List[Tuple[Tuple[str, str], ast.stmt]] = []
    for stmt in stmts:
        if hasattr(stmt, "body"):
            continue
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Call):
                continue
            pos = don.call_positions(fi, node, tuples)
            if pos is None:
                continue
            for arg in don.donated_args(fi, node, pos, tuples):
                b = _binding(arg)
                if b is not None:
                    donations.append((b, stmt))

    for binding, dstmt in donations:
        kind, name = binding
        label = name if kind == "name" else f"self.{name}"
        # closure escape: the donated binding captured by any nested
        # function in this function (runs later, buffer gone)
        for node in ast.walk(fi.node):
            if node is not fi.node and \
                    isinstance(node, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                body = node.body if isinstance(node.body, list) \
                    else [ast.Expr(node.body)]
                for s in body:
                    for ln in _reads_of(s, binding, skip_nested=False):
                        if sf.pragma_at(ln, "donate-ok"):
                            continue
                        findings.append(Finding(
                            RULE, fi.rel, ln, fi.qual,
                            f"donate-escape-closure:{label}",
                            f"`{label}` is donated in {fi.name} but "
                            "captured by a nested function — the closure "
                            "runs after the buffer is donated; pass the "
                            "value as an argument or rebind first"))
        # use-after-donate: linear scan past the donating statement
        if _rebinds(dstmt, binding):
            continue    # `x = entry(x, ...)`: rebound at the same stmt
        dead = False
        for stmt in stmts:
            if stmt is dstmt:
                dead = True
                continue
            if not dead or stmt.lineno <= dstmt.lineno:
                continue
            # compound statements: scan only the header expression
            # (test / iter) — their body leaves are in `stmts` already
            if hasattr(stmt, "body"):
                header = getattr(stmt, "test", None) \
                    or getattr(stmt, "iter", None)
                reads = _reads_of(ast.Expr(header), binding) \
                    if header is not None else []
                rebound = _rebinds(stmt, binding)
            else:
                reads = _reads_of(stmt, binding)
                rebound = _rebinds(stmt, binding)
            for ln in reads:
                if sf.pragma_at(ln, "donate-ok"):
                    continue
                findings.append(Finding(
                    RULE, fi.rel, ln, fi.qual,
                    f"use-after-donate:{label}",
                    f"`{label}` was donated into a dispatch above "
                    "(donate_argnums) and read here without a rebind — "
                    "on TPU the buffer now aliases the entry's output"))
            if rebound:
                break


# -- rule 3: device refs escaping into durable payloads -------------------

def _devicey_unlaundered(taint: _DeviceTaint, node: ast.AST) -> bool:
    """Device value NOT passed through a laundering conversion."""
    if isinstance(node, ast.Call):
        fd = dotted(node.func)
        if fd is not None and fd.split(".")[-1] in _LAUNDER_CALLS:
            return False
    if isinstance(node, (ast.Tuple, ast.List)):
        return any(_devicey_unlaundered(taint, e) for e in node.elts)
    if isinstance(node, ast.Dict):
        return any(_devicey_unlaundered(taint, v)
                   for v in node.values if v is not None)
    if isinstance(node, ast.ListComp):
        return _devicey_unlaundered(taint, node.elt)
    return taint.is_devicey(node)


def _check_escapes(pkg: Package, fi: FunctionInfo,
                   findings: List[Finding]) -> None:
    sf = pkg.files[fi.rel]
    taint = _DeviceTaint(pkg, fi.rel)
    for stmt in getattr(fi.node, "body", []):
        taint.visit(stmt)

    def flag(node: ast.AST, code: str, msg: str) -> None:
        if sf.pragma_at(node.lineno, "donate-ok"):
            return
        findings.append(Finding(RULE, fi.rel, node.lineno, fi.qual,
                                code, msg))

    is_ckpt = fi.name.split(".")[-1] in _CKPT_METHOD_NAMES
    for node in ast.walk(fi.node):
        # checkpoint payloads: every store into a subscripted dict and
        # every dict-literal value inside a checkpoint_state method
        if is_ckpt:
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Subscript) \
                            and _devicey_unlaundered(taint, node.value):
                        flag(node, "escape-checkpoint",
                             "device value stored into checkpoint state "
                             "— checkpoints must be device-ref-free "
                             "(np.asarray / device_get first)")
            if isinstance(node, ast.Dict):
                for v in node.values:
                    if v is not None and _devicey_unlaundered(taint, v):
                        flag(v, "escape-checkpoint",
                             "device value in a checkpoint_state payload "
                             "— checkpoints must be device-ref-free")
        if not isinstance(node, ast.Call):
            continue
        if isinstance(node.func, ast.Attribute):
            attr = node.func.attr
            if attr == _FLIGHT_DUMP_ATTR and len(node.args) >= 2 \
                    and _devicey_unlaundered(taint, node.args[1]):
                flag(node, "escape-flight",
                     "device value in a flight-recorder dump payload — "
                     "the bundle serializes after the buffer may be "
                     "donated; convert to host data first")
            elif attr in _TELEMETRY_CALLS and len(node.args) >= 2 \
                    and _devicey_unlaundered(taint, node.args[1]):
                flag(node, "escape-telemetry",
                     f"device value passed to {attr}() — telemetry "
                     "payloads outlive the iteration that produced "
                     "them; convert with float()/np.asarray first")


# -- rule 4: trailing-fetch handle drains ---------------------------------

def _pending_fetch_attrs(pkg: Package, methods: List[str]
                         ) -> Dict[str, int]:
    """attr -> first store line, for attrs holding async-copy refs."""
    out: Dict[str, int] = {}
    for q in methods:
        fi = pkg.functions[q]
        # receivers of .copy_to_host_async() + containers they enter
        refs: Set[str] = set()
        for node in ast.walk(fi.node):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "copy_to_host_async" \
                    and isinstance(node.func.value, ast.Name):
                refs.add(node.func.value.id)
        if not refs:
            continue
        def mentions(node: ast.AST) -> bool:
            return any(isinstance(n, ast.Name) and n.id in refs
                       for n in ast.walk(node))
        for node in ast.walk(fi.node):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("append", "add"):
                a = _self_attr(node.func.value)
                if a is not None and any(mentions(x) for x in node.args):
                    out.setdefault(a, node.lineno)
                elif isinstance(node.func.value, ast.Name) \
                        and any(mentions(x) for x in node.args):
                    refs.add(node.func.value.id)
            elif isinstance(node, ast.Assign) and mentions(node.value):
                for t in node.targets:
                    a = _self_attr(t)
                    if a is not None:
                        out.setdefault(a, node.lineno)
    return out


def _resets_attr(pkg: Package, qual: str, attr: str) -> bool:
    fi = pkg.functions[qual]
    for node in ast.walk(fi.node):
        if isinstance(node, ast.Assign) and \
                any(_self_attr(t) == attr for t in node.targets):
            return True
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "clear" \
                and _self_attr(node.func.value) == attr:
            return True
    return False


def _check_fetch_drains(pkg: Package, findings: List[Finding]) -> None:
    classes: Dict[Tuple[str, str], List[str]] = {}
    for qual, fi in pkg.functions.items():
        if fi.cls is not None and "." not in fi.name:
            classes.setdefault((fi.rel, fi.cls), []).append(qual)
    graph = pkg.call_graph()
    for (rel, cls), methods in sorted(classes.items()):
        pending = _pending_fetch_attrs(pkg, sorted(methods))
        if not pending:
            continue
        sf = pkg.files[rel]
        for attr, line in sorted(pending.items()):
            if sf.pragma_at(line, "donate-ok"):
                continue
            drains = [q for q in methods
                      if not q.endswith("__init__")
                      and _resets_attr(pkg, q, attr)]
            if not drains:
                findings.append(Finding(
                    RULE, rel, line, "", f"fetch-no-drain:{cls}.{attr}",
                    f"`self.{attr}` parks copy_to_host_async handles but "
                    f"no method of {cls} ever resets it — in-flight "
                    "fetches need a drain on finish/checkpoint/"
                    "quarantine paths"))
                continue
            # checkpoint discipline: checkpoint_state must reach a drain
            ckpts = [q for q in methods
                     if pkg.functions[q].name in _CKPT_METHOD_NAMES]
            for cq in ckpts:
                reach = pkg.reachable([cq])
                if not any(d in reach for d in drains):
                    findings.append(Finding(
                        RULE, rel, pkg.functions[cq].lineno, cq,
                        f"fetch-ckpt-live:{cls}.{attr}",
                        f"{cls}.checkpoint_state does not drain the "
                        f"in-flight fetch handles in `self.{attr}` — a "
                        "checkpoint must not carry live device refs "
                        "(the _drain_stop_check discipline)"))


# -- pack entry point -----------------------------------------------------

def check(pkg: Package) -> List[Finding]:
    findings: List[Finding] = []
    don = _Donations(pkg)
    for qual in sorted(pkg.functions):
        fi = pkg.functions[qual]
        # nested functions are scanned as part of their parent
        if "." in fi.name:
            continue
        _check_function_donations(pkg, don, fi, findings)
        _check_escapes(pkg, fi, findings)
    _check_fetch_drains(pkg, findings)
    # dedupe (closure-escape scan can revisit a line via ast.walk)
    seen: Set[Tuple[str, int, str]] = set()
    out: List[Finding] = []
    for f in findings:
        k = (f.path, f.line, f.code)
        if k in seen:
            continue
        seen.add(k)
        out.append(f)
    return out
