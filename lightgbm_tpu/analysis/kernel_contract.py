"""Rule pack: kernel-contract.

Per-`pallas_call` contract checks that fail only at Mosaic lowering
time on a real TPU (or worse, silently pad):

- **tile-lane / tile-sublane** — literal BlockSpec dims must respect
  the TPU register tiling: last dim a multiple of 128 (the lane
  width), second-to-last a multiple of 8 (f32/i32 sublane; int16/bf16
  need 16, int8 32 — the pack checks the weakest bound it can prove,
  see docs/STATIC_ANALYSIS.md for the table). Non-literal dims are
  trusted: the repo sizes blocks from `config.tpu_*` knobs that the
  runtime validates.
- **block-divisibility** — when `out_shape` and the out `BlockSpec`
  both carry literal dim tuples of the same rank, every shape dim must
  divide evenly by its block dim (Pallas pads the remainder block and
  the kernel reads garbage lanes).
- **out-dtype** — the dtype a kernel body stores into its out ref
  (`out_ref[...] = x.astype(...)`) must match the `ShapeDtypeStruct`
  dtype declared in `out_shape`; a mismatch means an implicit convert
  on every store.
- **memspace** — `pltpu.ANY` / `pltpu.TPUMemorySpace` references: the
  installed jax (0.9) no longer has them, so the kernel fails at import
  of the attribute on the chip. Unblocked HBM operands are spelled
  `pltpu.HBM`.
- **bitcast-width** — `lax.bitcast_convert_type(x, T)` where `x`'s
  dtype is statically known (an `.astype(S)` wrap or a prior
  bitcast/astype assignment in the same function) and `S`/`T` have
  different bit widths: the result grows/splits a trailing dim, which
  is occasionally intended (the packed-plane read) but never obvious.

Suppress a deliberate site with `# tpulint: tile-ok(<reason>)`.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .core import Finding, Package, dotted

_LANE = 128
_SUBLANE = 8

_DTYPE_BITS = {
    "float64": 64, "int64": 64, "uint64": 64,
    "float32": 32, "int32": 32, "uint32": 32,
    "float16": 16, "bfloat16": 16, "int16": 16, "uint16": 16,
    "int8": 8, "uint8": 8, "bool_": 8, "float8_e4m3fn": 8,
    "float8_e5m2": 8,
}

_REMOVED_MEMSPACES = ("ANY", "TPUMemorySpace")


def _pallas_aliases(tree: ast.Module) -> Tuple[Set[str], Set[str]]:
    """(pl aliases, pltpu aliases) — pallas imports are function-local
    in this repo, so scan the whole tree, not just module level."""
    pl_names: Set[str] = set()
    pltpu_names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.level == 0:
            if node.module == "jax.experimental":
                for al in node.names:
                    if al.name == "pallas":
                        pl_names.add(al.asname or "pallas")
            elif node.module == "jax.experimental.pallas":
                for al in node.names:
                    if al.name == "tpu":
                        pltpu_names.add(al.asname or "tpu")
        elif isinstance(node, ast.Import):
            for al in node.names:
                if al.name == "jax.experimental.pallas" and al.asname:
                    pl_names.add(al.asname)
                elif al.name == "jax.experimental.pallas.tpu" and al.asname:
                    pltpu_names.add(al.asname)
    return pl_names, pltpu_names


def _dtype_leaf(node: Optional[ast.AST]) -> Optional[str]:
    """'float32' from `jnp.float32` / `np.float32` / `"float32"`."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value if node.value in _DTYPE_BITS else None
    d = dotted(node)
    if d is not None:
        leaf = d.split(".")[-1]
        if leaf in _DTYPE_BITS:
            return leaf
    return None


def _literal_dims(node: Optional[ast.AST]) -> Optional[List[Optional[int]]]:
    """Dim list from a tuple/list literal; non-literal dims -> None
    entries. Returns None when `node` isn't a tuple/list at all."""
    if not isinstance(node, (ast.Tuple, ast.List)):
        return None
    out: List[Optional[int]] = []
    for e in node.elts:
        if isinstance(e, ast.Constant) and isinstance(e.value, int):
            out.append(e.value)
        else:
            out.append(None)
    return out


def _blockspec_dims(call: ast.Call) -> Optional[List[Optional[int]]]:
    """The block-shape tuple of a BlockSpec(...) call (first positional
    arg or block_shape= kwarg)."""
    spec = call.args[0] if call.args else None
    for kw in call.keywords:
        if kw.arg == "block_shape":
            spec = kw.value
    return _literal_dims(spec)


class _FileChecker:
    def __init__(self, pkg: Package, rel: str,
                 findings: List[Finding]) -> None:
        self.pkg = pkg
        self.rel = rel
        self.sf = pkg.files[rel]
        self.findings = findings
        self.pl, self.pltpu = _pallas_aliases(self.sf.tree)

    def _emit(self, node: ast.AST, code: str, message: str) -> None:
        if self.sf.pragma_at(node.lineno, "tile-ok"):
            return
        caller = self.pkg.enclosing_function(self.rel, node)
        self.findings.append(Finding(
            "kernel-contract", self.rel, node.lineno,
            caller.qual if caller else "", code, message))

    # -- tiling ----------------------------------------------------------
    def check_blockspec(self, call: ast.Call) -> None:
        dims = _blockspec_dims(call)
        if not dims:
            return
        lane = dims[-1]
        if lane is not None and lane % _LANE != 0:
            self._emit(call, f"tile-lane:{lane}",
                       f"BlockSpec last dim {lane} is not a multiple of "
                       f"the TPU lane width {_LANE}; the block pads to "
                       f"{_LANE} lanes and wastes the register file")
        if len(dims) >= 2:
            sub = dims[-2]
            if sub is not None and sub % _SUBLANE != 0:
                self._emit(call, f"tile-sublane:{sub}",
                           f"BlockSpec sublane dim {sub} is not a multiple "
                           f"of {_SUBLANE} (f32 min tile; int16/bf16 need "
                           "16, int8 32)")

    # -- pallas_call: divisibility + out dtype ---------------------------
    def check_pallas_call(self, call: ast.Call) -> None:
        out_shape_kw = out_specs_kw = None
        for kw in call.keywords:
            if kw.arg == "out_shape":
                out_shape_kw = kw.value
            elif kw.arg == "out_specs":
                out_specs_kw = kw.value
        sds_calls = [n for n in ast.walk(out_shape_kw)
                     if isinstance(n, ast.Call)
                     and (dotted(n.func) or "").split(".")[-1]
                     == "ShapeDtypeStruct"] if out_shape_kw else []
        if out_specs_kw is not None and len(sds_calls) == 1:
            spec_calls = [n for n in ast.walk(out_specs_kw)
                          if isinstance(n, ast.Call)
                          and (dotted(n.func) or "").split(".")[-1]
                          == "BlockSpec"]
            if len(spec_calls) == 1:
                shape = _literal_dims(sds_calls[0].args[0]
                                      if sds_calls[0].args else None)
                block = _blockspec_dims(spec_calls[0])
                if shape and block and len(shape) == len(block):
                    for i, (s, b) in enumerate(zip(shape, block)):
                        if s is not None and b is not None and b > 0 \
                                and s % b != 0:
                            self._emit(
                                spec_calls[0], f"block-divisibility:{i}",
                                f"out dim {i} = {s} is not divisible by "
                                f"its block dim {b}; Pallas pads the last "
                                "block and the kernel sees garbage rows")
        # out-dtype: declared ShapeDtypeStruct dtype vs kernel stores
        if len(sds_calls) == 1:
            decl = _dtype_leaf(
                sds_calls[0].args[1] if len(sds_calls[0].args) > 1 else
                next((kw.value for kw in sds_calls[0].keywords
                      if kw.arg == "dtype"), None))
            if decl is not None:
                self._check_kernel_stores(call, decl)

    def _kernel_quals(self, call: ast.Call) -> Set[str]:
        target = call.args[0] if call.args else None
        if isinstance(target, ast.Call):  # partial(kernel, ...)
            fd = dotted(target.func)
            if fd is not None and fd.split(".")[-1] == "partial" \
                    and target.args:
                target = target.args[0]
        if target is None or isinstance(target, ast.Lambda):
            return set()
        caller = self.pkg.enclosing_function(self.rel, call)
        return self.pkg.resolve_call(self.rel, caller, target,
                                     fallback=False)

    def _check_kernel_stores(self, call: ast.Call, decl: str) -> None:
        for q in self._kernel_quals(call):
            fi = self.pkg.functions.get(q)
            if fi is None:
                continue
            out_params = {p for p in fi.params
                          if "out" in p or p.startswith("o_")}
            if not out_params:
                continue
            for node in ast.walk(fi.node):
                if not (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Subscript)
                        and isinstance(node.targets[0].value, ast.Name)
                        and node.targets[0].value.id in out_params):
                    continue
                v = node.value
                if isinstance(v, ast.Call) \
                        and isinstance(v.func, ast.Attribute) \
                        and v.func.attr == "astype" and v.args:
                    stored = _dtype_leaf(v.args[0])
                    if stored is not None and stored != decl:
                        sf = self.pkg.files[fi.rel]
                        if sf.pragma_at(node.lineno, "tile-ok"):
                            continue
                        self.findings.append(Finding(
                            "kernel-contract", fi.rel, node.lineno, q,
                            f"out-dtype:{stored}-vs-{decl}",
                            f"kernel stores {stored} into an out ref "
                            f"declared {decl} in out_shape — implicit "
                            "convert on every store"))

    # -- memory space ----------------------------------------------------
    def check_memspace(self, node: ast.Attribute) -> None:
        if node.attr in _REMOVED_MEMSPACES \
                and isinstance(node.value, ast.Name) \
                and node.value.id in self.pltpu:
            self._emit(node, f"memspace:{node.attr}",
                       f"pltpu.{node.attr} does not exist in the "
                       "installed jax; use pltpu.HBM")

    # -- bitcast width ---------------------------------------------------
    def _source_dtype(self, expr: ast.AST,
                      fn_node: Optional[ast.AST],
                      before_line: int) -> Optional[str]:
        """dtype of `expr` when statically evident: an `.astype(S)` /
        bitcast wrap, or a Name whose latest assignment before
        `before_line` in the enclosing function is such a wrap."""
        if isinstance(expr, ast.Call):
            if isinstance(expr.func, ast.Attribute) \
                    and expr.func.attr == "astype" and expr.args:
                return _dtype_leaf(expr.args[0])
            fd = dotted(expr.func)
            if fd is not None \
                    and fd.split(".")[-1] == "bitcast_convert_type" \
                    and len(expr.args) > 1:
                return _dtype_leaf(expr.args[1])
            return None
        if isinstance(expr, ast.Name) and fn_node is not None:
            best: Optional[Tuple[int, Optional[str]]] = None
            for n in ast.walk(fn_node):
                if isinstance(n, ast.Assign) and n.lineno < before_line \
                        and any(isinstance(t, ast.Name) and t.id == expr.id
                                for t in n.targets):
                    dt = self._source_dtype(n.value, None, before_line)
                    if best is None or n.lineno > best[0]:
                        best = (n.lineno, dt)
            return best[1] if best else None
        return None

    def check_bitcast(self, call: ast.Call) -> None:
        if len(call.args) < 2:
            return
        dst = _dtype_leaf(call.args[1])
        if dst is None:
            return
        caller = self.pkg.enclosing_function(self.rel, call)
        src = self._source_dtype(call.args[0],
                                 caller.node if caller else None,
                                 call.lineno)
        if src is None:
            return
        if _DTYPE_BITS[src] != _DTYPE_BITS[dst]:
            self._emit(call, f"bitcast-width:{src}->{dst}",
                       f"bitcast_convert_type {src} ({_DTYPE_BITS[src]}b) "
                       f"-> {dst} ({_DTYPE_BITS[dst]}b) changes the bit "
                       "width: the result gains/splits a trailing dim")

    # -- driver ----------------------------------------------------------
    def run(self) -> None:
        for node in ast.walk(self.sf.tree):
            if isinstance(node, ast.Call):
                d = dotted(node.func)
                leaf = d.split(".")[-1] if d else None
                if leaf == "BlockSpec":
                    self.check_blockspec(node)
                elif leaf == "pallas_call":
                    self.check_pallas_call(node)
                elif leaf == "bitcast_convert_type":
                    self.check_bitcast(node)
            elif isinstance(node, ast.Attribute):
                self.check_memspace(node)


def check(pkg: Package) -> List[Finding]:
    findings: List[Finding] = []
    for rel in sorted(pkg.files):
        _FileChecker(pkg, rel, findings).run()
    return findings
