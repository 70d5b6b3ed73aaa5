"""Shared AST helpers for class-level passes."""

from __future__ import annotations

import ast
from typing import Optional

FunctionNode = (ast.FunctionDef, ast.AsyncFunctionDef)


def self_name(fn) -> Optional[str]:
    """The instance-receiver arg name, or None for static/class methods
    (a classmethod's first arg binds the type — attribute reads on it
    resolve against class attributes, out of scope here)."""
    for dec in fn.decorator_list:
        name = dec.id if isinstance(dec, ast.Name) else getattr(dec, "attr", "")
        if name in ("staticmethod", "classmethod"):
            return None
    args = fn.args.posonlyargs + fn.args.args
    return args[0].arg if args else None


def methods_of(cls: ast.ClassDef) -> dict[str, ast.FunctionDef]:
    return {n.name: n for n in cls.body if isinstance(n, FunctionNode)}


def class_level_names(cls: ast.ClassDef) -> set[str]:
    out: set[str] = set()
    for n in cls.body:
        if isinstance(n, ast.Assign):
            out |= {t.id for t in n.targets if isinstance(t, ast.Name)}
        elif isinstance(n, ast.AnnAssign) and isinstance(n.target, ast.Name):
            out.add(n.target.id)
    return out


def attr_stores(fn) -> set[str]:
    """Names assigned as `self.x = ...` (tuple targets included) anywhere in
    the function. AugAssign does NOT count — `self.x += 1` requires a prior
    binding, i.e. it is a read."""
    me = self_name(fn)
    out: set[str] = set()
    if me is None:
        return out
    for node in ast.walk(fn):
        targets: list[ast.expr] = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        for t in targets:
            for tt in ast.walk(t):
                if (isinstance(tt, ast.Attribute)
                        and isinstance(tt.value, ast.Name)
                        and tt.value.id == me):
                    out.add(tt.attr)
    return out


def attr_reads(fn) -> dict[str, int]:
    """{attr: first line} for `self.x` loads (and AugAssign reads)."""
    me = self_name(fn)
    out: dict[str, int] = {}
    if me is None:
        return out
    for node in ast.walk(fn):
        attr = None
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == me):
            if isinstance(node.ctx, ast.Load):
                attr = node.attr
            elif isinstance(node.ctx, ast.Store):
                continue
        if isinstance(node, ast.AugAssign):
            t = node.target
            if (isinstance(t, ast.Attribute) and isinstance(t.value, ast.Name)
                    and t.value.id == me):
                attr = t.attr
        if attr is not None:
            out.setdefault(attr, node.lineno)
    return out


def self_calls(fn) -> set[str]:
    """Method names invoked as `self.m(...)` — the intra-class call graph."""
    me = self_name(fn)
    out: set[str] = set()
    if me is None:
        return out
    for node in ast.walk(fn):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == me):
            out.add(node.func.attr)
    return out


def hasattr_probes(cls: ast.ClassDef) -> set[str]:
    """Attr names checked via hasattr(self, "x") anywhere in the class."""
    out: set[str] = set()
    for node in ast.walk(cls):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "hasattr" and len(node.args) == 2
                and isinstance(node.args[1], ast.Constant)
                and isinstance(node.args[1].value, str)):
            out.add(node.args[1].value)
    return out


def construction_methods(methods: dict[str, ast.FunctionDef]) -> set[str]:
    """__init__ plus every method it (transitively) calls on self — no
    second thread exists while these run."""
    seen: set[str] = set()
    frontier = ["__init__"]
    while frontier:
        name = frontier.pop()
        if name in seen or name not in methods:
            continue
        seen.add(name)
        frontier.extend(self_calls(methods[name]))
    return seen


def construction_assigned(cls: ast.ClassDef,
                          module_classes: Optional[dict] = None) -> set[str]:
    """Attributes assigned during construction: class level, __init__, and
    every method __init__ transitively calls on self. Method/property names
    count (they resolve on the type). When `module_classes` ({name: node})
    is given, same-module base classes contribute their construction too
    (super().__init__ runs their assignments)."""
    methods = methods_of(cls)
    assigned = class_level_names(cls) | set(methods)
    for name in construction_methods(methods):
        assigned |= attr_stores(methods[name])
    if module_classes:
        for base in cls.bases:
            bname = base.id if isinstance(base, ast.Name) else getattr(base, "attr", "")
            bcls = module_classes.get(bname)
            if bcls is not None and bcls is not cls:
                assigned |= construction_assigned(bcls, module_classes)
    return assigned


def dotted_name(node: ast.AST) -> str:
    """'jnp.zeros' for Attribute/Name chains, '' for anything else."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""
