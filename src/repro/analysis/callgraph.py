"""Lightweight module/class/function index and jit-set call-graph walk.

This is not a general Python call graph — it is exactly the resolution
the repo's contracts need, tuned to the codebase's idioms:

* absolute imports rooted at the analyzed package (``from repro.core.api
  import make_ctx``) plus one level of package re-export
  (``kernels/extend_fused/__init__.py``-style);
* class attribute seams (``_pruned_kernel = staticmethod(fn)``) and
  ``super()`` dispatch resolved against the *concrete* receiver class,
  so a ``grid_contract="concurrent"`` subclass reaches its own kernel
  substitution, not its parent's;
* the ``traceable`` class flag: classes declaring ``traceable = False``
  (the host capacity policy) are never entered by the traced-set walk —
  the codebase's own host/jit seam is the analyzer's, too;
* host-guard awareness: statements under ``if host:`` /
  ``if not policy.traceable:`` / ``if _T.on:`` / ``if collect_stats:``
  (and the early-``return`` form) are host-only regions — the walk
  neither reports violations there nor follows calls out of them.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Optional

from repro.analysis.core import Project, SourceFile

# Names whose truthiness marks a host-only (or obs-enabled) region.
HOST_GUARD_NAMES = {"host", "collect_stats", "checkpoint_cb"}
HOST_GUARD_ATTRS = {"traceable", "on"}


@dataclasses.dataclass
class FuncInfo:
    qualname: str                 # module-relative dotted qualname
    module: str                   # dotted module name
    node: ast.AST                 # FunctionDef / AsyncFunctionDef
    sf: SourceFile
    cls: Optional["ClassInfo"] = None


@dataclasses.dataclass
class ClassInfo:
    name: str
    module: str
    node: ast.ClassDef
    sf: SourceFile
    bases: list[ast.expr] = dataclasses.field(default_factory=list)
    methods: dict = dataclasses.field(default_factory=dict)
    attrs: dict = dataclasses.field(default_factory=dict)  # name -> expr


@dataclasses.dataclass
class ModInfo:
    name: str
    sf: SourceFile
    functions: dict = dataclasses.field(default_factory=dict)
    classes: dict = dataclasses.field(default_factory=dict)
    # local name -> ("mod", dotted) | ("obj", dotted, original_name)
    imports: dict = dataclasses.field(default_factory=dict)


class ProjectIndex:
    """Symbol tables for every module in a :class:`Project`."""

    def __init__(self, project: Project):
        self.project = project
        self.modules: dict[str, ModInfo] = {}
        for sf in project.files:
            name = project.module_name(sf)
            self.modules[name] = self._index_module(name, sf)

    # -- indexing ----------------------------------------------------------

    def _index_module(self, name: str, sf: SourceFile) -> ModInfo:
        mod = ModInfo(name=name, sf=sf)
        for node in sf.tree.body:
            self._index_stmt(mod, node)
        return mod

    def _index_stmt(self, mod: ModInfo, node: ast.stmt) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            mod.functions[node.name] = FuncInfo(node.name, mod.name, node,
                                                mod.sf)
        elif isinstance(node, ast.ClassDef):
            ci = ClassInfo(name=node.name, module=mod.name, node=node,
                           sf=mod.sf, bases=list(node.bases))
            for item in node.body:
                if isinstance(item, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    ci.methods[item.name] = FuncInfo(
                        f"{node.name}.{item.name}", mod.name, item,
                        mod.sf, cls=ci)
                elif isinstance(item, ast.Assign):
                    for tgt in item.targets:
                        if isinstance(tgt, ast.Name):
                            ci.attrs[tgt.id] = item.value
                elif (isinstance(item, ast.AnnAssign)
                      and isinstance(item.target, ast.Name)
                      and item.value is not None):
                    ci.attrs[item.target.id] = item.value
            mod.classes[node.name] = ci
        elif isinstance(node, ast.Import):
            for alias in node.names:
                local = alias.asname or alias.name.split(".")[0]
                target = alias.name if alias.asname else \
                    alias.name.split(".")[0]
                mod.imports[local] = ("mod", target)
        elif isinstance(node, ast.ImportFrom):
            base = self._resolve_from(mod.name, node)
            if base is None:
                return
            for alias in node.names:
                local = alias.asname or alias.name
                mod.imports[local] = ("obj", base, alias.name)
        elif isinstance(node, (ast.If, ast.Try)):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.stmt):
                    self._index_stmt(mod, child)

    def _resolve_from(self, modname: str,
                      node: ast.ImportFrom) -> Optional[str]:
        if node.level == 0:
            return node.module
        parts = modname.split(".")
        # a module's package is itself for __init__ (its name has no
        # trailing file component in our dotted scheme) — approximate
        # with the filename: packages end the dotted name at the dir
        sf = self.modules.get(modname)
        is_pkg = sf is not None and sf.sf.rel.endswith("__init__.py")
        cut = len(parts) - (node.level - 1 if is_pkg else node.level)
        if cut < 1:
            return None
        base = parts[:cut]
        if node.module:
            base.append(node.module)
        return ".".join(base)

    # -- symbol resolution -------------------------------------------------

    def resolve_name(self, modname: str, name: str, _depth: int = 0):
        """A name visible in ``modname`` -> FuncInfo | ClassInfo | None."""
        mod = self.modules.get(modname)
        if mod is None or _depth > 8:
            return None
        if name in mod.functions:
            return mod.functions[name]
        if name in mod.classes:
            return mod.classes[name]
        imp = mod.imports.get(name)
        if imp is None:
            return None
        if imp[0] == "mod":
            return None
        _, target_mod, orig = imp
        return self.resolve_name(target_mod, orig, _depth + 1)

    def resolve_base(self, ci: ClassInfo,
                     base: ast.expr) -> Optional[ClassInfo]:
        if isinstance(base, ast.Name):
            out = self.resolve_name(ci.module, base.id)
        elif isinstance(base, ast.Attribute) and isinstance(base.value,
                                                            ast.Name):
            mod = self.modules.get(ci.module)
            imp = mod.imports.get(base.value.id) if mod else None
            out = (self.resolve_name(imp[1], base.attr)
                   if imp and imp[0] == "mod" else None)
        else:
            out = None
        return out if isinstance(out, ClassInfo) else None

    def mro(self, ci: ClassInfo) -> list[ClassInfo]:
        """Own-class-first linearization (good enough: single bases)."""
        out, stack, seen = [], [ci], set()
        while stack:
            c = stack.pop(0)
            if id(c) in seen:
                continue
            seen.add(id(c))
            out.append(c)
            for b in c.bases:
                rb = self.resolve_base(c, b)
                if rb is not None:
                    stack.append(rb)
        return out

    def effective_attr(self, ci: ClassInfo, name: str):
        for c in self.mro(ci):
            if name in c.attrs:
                return c.attrs[name]
        return None

    def effective_method(self, ci: ClassInfo,
                         name: str) -> Optional[FuncInfo]:
        for c in self.mro(ci):
            if name in c.methods:
                return c.methods[name]
        return None

    def inherits_from(self, ci: ClassInfo, base_name: str) -> bool:
        return any(c.name == base_name for c in self.mro(ci))

    def const_attr(self, ci: ClassInfo, name: str):
        """Effective class attr as a Python constant, else None."""
        expr = self.effective_attr(ci, name)
        if isinstance(expr, ast.Constant):
            return expr.value
        return None

    def all_classes(self):
        for mod in self.modules.values():
            yield from mod.classes.values()

    def all_functions(self):
        """Every function/method, including nested defs."""
        for mod in self.modules.values():
            sf = mod.sf
            for node in ast.walk(sf.tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    yield mod, node

    def visible_classes(self, modname: str) -> list[ClassInfo]:
        """Classes defined in or imported into ``modname``."""
        mod = self.modules.get(modname)
        if mod is None:
            return []
        out = list(mod.classes.values())
        for imp in mod.imports.values():
            if imp[0] == "obj":
                got = self.resolve_name(imp[1], imp[2])
                if isinstance(got, ClassInfo):
                    out.append(got)
        return out


# ---------------------------------------------------------------------------
# Host-guard-aware traversal


def is_host_guard(test: ast.expr) -> bool:
    """Does ``test`` condition on a host/obs flag the warm path pins?"""
    for node in ast.walk(test):
        if isinstance(node, ast.Name) and node.id in HOST_GUARD_NAMES:
            return True
        if isinstance(node, ast.Attribute) and node.attr in \
                HOST_GUARD_ATTRS:
            return True
    return False


def _terminates(body: list[ast.stmt]) -> bool:
    return bool(body) and isinstance(
        body[-1], (ast.Return, ast.Raise, ast.Continue, ast.Break))


def iter_unguarded(node: ast.AST, *, skip_nested: bool = True):
    """Yield descendants of ``node`` outside host-guarded regions.

    Skips ``if <host-guard>:`` statements wholesale (both branches are
    picked by a flag the warm path pins statically); a guarded early
    return (``if not _T.on: return ...``) additionally ends the scan of
    the remaining statements in that block, which are then the
    obs-enabled slow path.  With ``skip_nested`` (default) nested
    function/class definitions are yielded but not entered — they are
    separate call-graph nodes.
    """
    for _field, value in ast.iter_fields(node):
        if isinstance(value, list):
            stop = False
            for item in value:
                if stop or not isinstance(item, ast.AST):
                    continue
                if isinstance(item, ast.If) and is_host_guard(item.test):
                    if _terminates(item.body):
                        stop = True
                    continue
                if isinstance(item, ast.IfExp) and \
                        is_host_guard(item.test):
                    continue
                yield item
                if skip_nested and isinstance(
                        item, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef, ast.Lambda)):
                    continue
                yield from iter_unguarded(item, skip_nested=skip_nested)
        elif isinstance(value, ast.AST):
            if isinstance(value, ast.IfExp) and is_host_guard(value.test):
                continue
            yield value
            if skip_nested and isinstance(
                    value, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef, ast.Lambda)):
                continue
            yield from iter_unguarded(value, skip_nested=skip_nested)


def local_defs(fn_node: ast.AST) -> dict[str, ast.AST]:
    """Directly nested function definitions of ``fn_node`` by name."""
    out = {}
    for item in ast.walk(fn_node):
        if item is fn_node:
            continue
        if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.setdefault(item.name, item)
    return out


# ---------------------------------------------------------------------------
# The jit-traced set


def _call_name(fn: ast.expr):
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return None


def jit_argument_names(tree: ast.AST) -> list[tuple[str, ast.AST]]:
    """Names syntactically handed to ``jax.jit`` / ``pallas_call`` /
    ``shard_map`` (directly or through ``partial``) plus jit decorators.

    Returns ``(name, context_node)`` pairs; names resolve in the scope
    of the context node's enclosing function or module.
    """
    out = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _decorator_is_jit(dec):
                    out.append((node.name, node))
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node.func)
        if name not in ("jit", "pallas_call", "shard_map"):
            continue
        args = list(node.args)
        if not args:
            continue
        target = args[0]
        if isinstance(target, ast.Call) and \
                _call_name(target.func) == "partial" and target.args:
            target = target.args[0]
        if isinstance(target, ast.Name):
            out.append((target.id, node))
    return out


def _decorator_is_jit(dec: ast.expr) -> bool:
    # @jax.jit | @jit | @partial(jax.jit, ...) | @jax.jit(...)
    if _call_name(dec) == "jit":
        return True
    if isinstance(dec, ast.Call):
        name = _call_name(dec.func)
        if name == "jit":
            return True
        if name == "partial" and dec.args and \
                _call_name(dec.args[0]) == "jit":
            return True
    return False


class TracedSet:
    """Functions reachable from the jit-traced roots, guard-aware.

    Roots: functions handed to ``jax.jit``/``pallas_call``/``shard_map``,
    jit-decorated functions, the engine's named entry points, methods of
    ``traceable = True`` policy classes, op methods of ``PhaseBackend``
    descendants, and everything defined under ``kernels/``.  The walk
    follows name, import, ``self``/``super`` and method-name attribute
    calls; it never enters host-marked modules or ``traceable = False``
    classes, and never follows calls out of host-guarded regions.
    """

    NAMED_ROOTS = ("run_level_loop", "bounded_mine_vertex",
                   "bounded_mine_edge")
    BACKEND_BASE = "PhaseBackend"
    NON_OP_METHODS = {"capabilities", "__repr__", "__init__"}

    def __init__(self, idx: ProjectIndex):
        self.idx = idx
        # id(node) -> (FuncInfo-ish record) for every traced function
        self.traced: dict[int, tuple[ast.AST, SourceFile, str,
                                     Optional[ClassInfo]]] = {}
        self._walk()

    # -- roots -------------------------------------------------------------

    def _roots(self):
        idx = self.idx
        roots: list[tuple[ast.AST, SourceFile, str,
                          Optional[ClassInfo]]] = []
        for modname, mod in idx.modules.items():
            sf = mod.sf
            if sf.is_host_module:
                continue
            in_kernels = "kernels/" in sf.rel.replace("\\", "/") or \
                sf.rel.replace("\\", "/").startswith("kernels")
            if in_kernels:
                for node in ast.walk(sf.tree):
                    if isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        roots.append((node, sf, modname, None))
            for name, ctx_node in jit_argument_names(sf.tree):
                fn = self._resolve_jit_name(mod, name, ctx_node)
                if fn is not None:
                    roots.append((fn, sf, modname, None))
            for fname in self.NAMED_ROOTS:
                fi = mod.functions.get(fname)
                if fi is not None:
                    roots.append((fi.node, sf, modname, None))
            for ci in mod.classes.values():
                traceable = idx.const_attr(ci, "traceable")
                is_backend = idx.inherits_from(ci, self.BACKEND_BASE)
                if traceable is True or is_backend:
                    for mname, mi in ci.methods.items():
                        if is_backend and mname in self.NON_OP_METHODS:
                            continue
                        roots.append((mi.node, sf, modname, ci))
        return roots

    def _resolve_jit_name(self, mod: ModInfo, name: str,
                          ctx_node: ast.AST) -> Optional[ast.AST]:
        # nearest enclosing function's nested defs win; else module scope
        for node in ast.walk(mod.sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(n is ctx_node for n in ast.walk(node)):
                    nested = local_defs(node)
                    if name in nested:
                        return nested[name]
        got = self.idx.resolve_name(mod.name, name)
        if isinstance(got, FuncInfo):
            return got.node
        return None

    # -- reachability ------------------------------------------------------

    def _walk(self) -> None:
        stack = list(self._roots())
        while stack:
            node, sf, modname, cls = stack.pop()
            if id(node) in self.traced:
                continue
            self.traced[id(node)] = (node, sf, modname, cls)
            for callee in self.callees(node, sf, modname, cls):
                stack.append(callee)

    def callees(self, fn_node: ast.AST, sf: SourceFile, modname: str,
                cls: Optional[ClassInfo]):
        """Resolved (node, sf, modname, cls) callees of one function."""
        idx = self.idx
        nested = local_defs(fn_node)
        for node in iter_unguarded(fn_node):
            if not isinstance(node, ast.Call):
                continue
            for tgt in resolve_call(idx, node, sf, modname, cls, nested):
                tnode, tsf, tmod, tcls = tgt
                if tsf.is_host_module:
                    continue
                tci = tcls if tcls is not None else None
                if tci is not None and \
                        idx.const_attr(tci, "traceable") is False:
                    continue
                yield tgt

    def __contains__(self, fn_node: ast.AST) -> bool:
        return id(fn_node) in self.traced

    def items(self):
        return list(self.traced.values())


def resolve_call(idx: ProjectIndex, call: ast.Call, sf: SourceFile,
                 modname: str, cls: Optional[ClassInfo], nested: dict):
    """Best-effort targets of one call: (node, sf, module, cls) tuples.

    Resolution order mirrors the codebase's dispatch idioms: nested
    defs, module/import names, ``super()``/``self`` with receiver-class
    binding (including ``staticmethod`` class-attr seams), imported
    submodule attributes, then method-name matching over classes
    visible in the calling module.
    """
    fn = call.func
    out = []

    def add_funcinfo(fi):
        if isinstance(fi, FuncInfo):
            mod = idx.modules.get(fi.module)
            if mod is not None:
                out.append((fi.node, mod.sf, fi.module, fi.cls))
        elif isinstance(fi, ClassInfo):
            if idx.const_attr(fi, "traceable") is False:
                return
            init = idx.effective_method(fi, "__init__")
            if init is not None:
                mod = idx.modules.get(init.module)
                if mod is not None:
                    out.append((init.node, mod.sf, init.module, fi))

    if isinstance(fn, ast.Name):
        if fn.id in nested:
            out.append((nested[fn.id], sf, modname, cls))
        else:
            add_funcinfo(idx.resolve_name(modname, fn.id))
    elif isinstance(fn, ast.Attribute):
        recv = fn.value
        # super().m(...) -> parent method, receiver class preserved
        if isinstance(recv, ast.Call) and \
                _call_name(recv.func) == "super" and cls is not None:
            for c in idx.mro(cls)[1:]:
                if fn.attr in c.methods:
                    mi = c.methods[fn.attr]
                    mod = idx.modules.get(mi.module)
                    if mod is not None:
                        out.append((mi.node, mod.sf, mi.module, cls))
                    break
        elif isinstance(recv, ast.Name) and recv.id == "self" and \
                cls is not None:
            mi = idx.effective_method(cls, fn.attr)
            if mi is not None:
                mod = idx.modules.get(mi.module)
                if mod is not None:
                    out.append((mi.node, mod.sf, mi.module, cls))
            else:
                # class-attr seam: self._kernel = staticmethod(fn)
                expr = idx.effective_attr(cls, fn.attr)
                name = _attr_value_name(expr)
                if name is not None:
                    add_funcinfo(idx.resolve_name(cls.module, name))
        elif isinstance(recv, ast.Name):
            mod = idx.modules.get(modname)
            imp = mod.imports.get(recv.id) if mod else None
            if imp is not None and imp[0] == "mod":
                add_funcinfo(idx.resolve_name(imp[1], fn.attr))
            else:
                for ci in idx.visible_classes(modname):
                    mi = idx.effective_method(ci, fn.attr)
                    if mi is not None and \
                            idx.const_attr(ci, "traceable") is not \
                            False:
                        mod2 = idx.modules.get(mi.module)
                        if mod2 is not None:
                            out.append((mi.node, mod2.sf, mi.module,
                                        ci))
        else:
            for ci in idx.visible_classes(modname):
                mi = idx.effective_method(ci, fn.attr)
                if mi is not None and \
                        idx.const_attr(ci, "traceable") is not False:
                    mod2 = idx.modules.get(mi.module)
                    if mod2 is not None:
                        out.append((mi.node, mod2.sf, mi.module, ci))
    return out


def _attr_value_name(expr) -> Optional[str]:
    """``staticmethod(fn)`` / plain ``fn`` class-attr value -> ``"fn"``."""
    if isinstance(expr, ast.Call) and _call_name(expr.func) in (
            "staticmethod", "classmethod") and expr.args:
        expr = expr.args[0]
    if isinstance(expr, ast.Name):
        return expr.id
    return None
