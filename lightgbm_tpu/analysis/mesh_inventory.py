"""meshlint shared infrastructure: the mesh/axis inventory.

Answers two questions the device-side rule packs (collective-axis,
kernel-contract, dtype-flow) all need, from `ast` alone:

- which mesh axis names exist in this package (`axis_inventory`):
  string literals in `Mesh(devices, ("data",))` constructions /
  `axis_names=` kwargs, plus the axis literals named in
  `shard_map`/`pmap` partition specs. `dynamic` is set when a mesh is
  built with non-literal axis names (`f"axis{i}"` in
  `treelearner/parallel.py:build_mesh`) — those are accepted when they
  match the `axis<N>` pattern.
- which functions run *inside* a mapped region (`mapped_bodies`):
  every body handed to `shard_map` / `pmap`, in any of the repo's
  spellings — decorator, `functools.partial(shard_map, ...)` decorator,
  direct `shard_map(f, ...)` call, and the
  `functools.partial(shard_map, ...)(body)` call form. Any alias is
  recognized by leaf name, the same over-approximation trace_safety
  uses. Deliberately NOT recognized:
  `@lambda f: shard_map(f, ...)` decorators — an anonymous wrapper the
  call graph cannot see through; write the explicit call form instead.

Inside a mapped body every axis of the mesh is bound, so binding is
tracked per-package (the inventory), not per-site; reachability from
any mapped body is what the collective-axis pack checks.
"""
from __future__ import annotations

import ast
import dataclasses
import re
from typing import Dict, List, Optional, Set, Tuple

from .core import FunctionInfo, Package, dotted

_DYNAMIC_AXIS_RE = re.compile(r"axis\d+")

# kwargs of a shard_map/pmap site that carry axis-name literals
_SPEC_KWARGS = ("in_specs", "out_specs", "axis_name", "axis_names")


@dataclasses.dataclass
class AxisInventory:
    axes: Set[str]                       # literal axis names
    dynamic: bool                        # a Mesh uses computed axis names
    meshes: List[Tuple[str, int]]        # (rel, line) of Mesh constructions

    def permits(self, name: str) -> bool:
        """Is `name` a plausible axis of some mesh in this package?"""
        if name in self.axes:
            return True
        return self.dynamic and _DYNAMIC_AXIS_RE.fullmatch(name) is not None


def _axis_literals(node: ast.AST) -> Tuple[Set[str], bool]:
    """(string literals, saw-non-literal) anywhere under `node`."""
    names: Set[str] = set()
    non_literal = False
    for n in ast.walk(node):
        if isinstance(n, ast.Constant):
            if isinstance(n.value, str):
                names.add(n.value)
        elif isinstance(n, (ast.JoinedStr, ast.BinOp, ast.GeneratorExp,
                            ast.ListComp)):
            non_literal = True
    return names, non_literal


def axis_inventory(pkg: Package) -> AxisInventory:
    axes: Set[str] = set()
    dynamic = False
    meshes: List[Tuple[str, int]] = []
    for rel, sf in pkg.files.items():
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func)
            leaf = d.split(".")[-1] if d else None
            if leaf == "Mesh":
                meshes.append((rel, node.lineno))
                spec: Optional[ast.AST] = None
                if len(node.args) >= 2:
                    spec = node.args[1]
                for kw in node.keywords:
                    if kw.arg == "axis_names":
                        spec = kw.value
                if spec is not None:
                    if isinstance(spec, (ast.Tuple, ast.List, ast.Constant)):
                        names, non_lit = _axis_literals(spec)
                        axes |= names
                        dynamic = dynamic or non_lit
                    else:
                        # axis names computed elsewhere (build_mesh's
                        # `axes = tuple(f"axis{i}" ...) + ("data",)`
                        # variable): treat as dynamic, and pick up any
                        # literals for the expression forms
                        names, _ = _axis_literals(spec)
                        axes |= names
                        dynamic = True
            elif leaf in ("shard_map", "pmap"):
                for kw in node.keywords:
                    if kw.arg in _SPEC_KWARGS:
                        names, _ = _axis_literals(kw.value)
                        axes |= names
    return AxisInventory(axes, dynamic, meshes)


def _is_mapping_name(node: ast.AST) -> Optional[str]:
    """'shard_map' | 'pmap' when `node` names that transform (any
    alias/attribute spelling)."""
    d = dotted(node)
    if d is None:
        return None
    leaf = d.split(".")[-1]
    return leaf if leaf in ("shard_map", "pmap") else None


def mapped_bodies(pkg: Package) -> Dict[str, int]:
    """qual -> definition line, for every function that is the body of a
    `shard_map`/`pmap` site. These are the roots from which collectives
    are legitimately reachable."""
    out: Dict[str, int] = {}

    def add(rel: str, caller: Optional[FunctionInfo],
            target: ast.AST) -> None:
        if isinstance(target, ast.Lambda):
            return  # collectives in lambda bodies get no qualname anyway
        for q in pkg.resolve_call(rel, caller, target, fallback=False):
            fi = pkg.functions.get(q)
            if fi is not None:
                out[q] = fi.lineno

    for rel, sf in pkg.files.items():
        # decorator forms: @shard_map-ish / @functools.partial(shard_map,..)
        for qual, fi in pkg.functions.items():
            if fi.rel != rel:
                continue
            for dec in getattr(fi.node, "decorator_list", []):
                if _is_mapping_name(dec) is not None:
                    out[qual] = fi.lineno
                    continue
                if isinstance(dec, ast.Call):
                    if _is_mapping_name(dec.func) is not None:
                        out[qual] = fi.lineno
                        continue
                    fd = dotted(dec.func)
                    if fd is not None and fd.split(".")[-1] == "partial" \
                            and dec.args \
                            and _is_mapping_name(dec.args[0]) is not None:
                        out[qual] = fi.lineno
        # call forms: shard_map(f, ...) / partial(shard_map, ...)(body)
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            caller = pkg.enclosing_function(rel, node)
            if _is_mapping_name(node.func) is not None and node.args:
                add(rel, caller, node.args[0])
            elif isinstance(node.func, ast.Call):
                fd = dotted(node.func.func)
                if fd is not None and fd.split(".")[-1] == "partial" \
                        and node.func.args \
                        and _is_mapping_name(node.func.args[0]) is not None \
                        and node.args:
                    add(rel, caller, node.args[0])
    return out


def self_attr_constants(pkg: Package) -> Dict[str, Set[object]]:
    """attr name -> set of constant values ever assigned package-wide as
    `self.<attr> = <constant>`. Used to resolve attribute axis
    arguments (`self.psum_axis`) at collective sites; a non-constant
    assignment poisons the attr (maps to {Ellipsis} marker)."""
    out: Dict[str, Set[object]] = {}
    for sf in pkg.files.values():
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Assign):
                continue
            for tgt in node.targets:
                if isinstance(tgt, ast.Attribute) \
                        and isinstance(tgt.value, ast.Name) \
                        and tgt.value.id == "self":
                    if isinstance(node.value, ast.Constant):
                        out.setdefault(tgt.attr, set()).add(node.value.value)
                    else:
                        out.setdefault(tgt.attr, set()).add(Ellipsis)
    return out
