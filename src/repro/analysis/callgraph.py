"""Project-wide call graph rooted at JAX trace regions.

Builds, from ASTs alone (nothing is imported), a conservative call
graph over every function in the scanned tree, marking the **traced
roots**: functions that enter a JAX trace —

  * decorated ``@jax.jit`` / ``@functools.partial(jax.jit, ...)`` /
    ``@jax.vmap`` / ``@jax.checkpoint``;
  * passed callable-first to a trace wrapper call: ``jax.jit(f)``,
    ``jax.vmap(f)``, ``jax.lax.scan(f, ...)``, ``pl.pallas_call(f)``,
    ``shard_map(f, ...)`` — including lambdas and nested ``def``s.

Edges follow direct calls: bare names (nested defs, then module
globals), ``from x import f`` bindings, and ``mod.f`` where ``mod`` is
an imported project module.  Method calls through objects are not
resolved (conservative under-approximation: the passes that consume
the graph flag what they can prove, never guess).

**Trace-guard pruning**: statements after ``if _traced(...): raise``
(or an ``isinstance(x, jax.core.Tracer)`` test that raises) in the same
block are *host-only* — a traced execution cannot reach them — so calls
there do not extend traced reachability.  This is exactly the
`kernels/*/ops.py` dispatch contract (`docs/kernels.md`): the host-impl
branch is fenced off by a raising trace check, and
`repro.analysis.trace_purity` separately verifies the fence exists.
"""
from __future__ import annotations

import ast
import dataclasses

from repro.analysis.core import Module, dotted, import_map

# Normalized dotted names whose first callable argument enters a trace.
TRACE_WRAPPERS = {
    "jax.jit", "jax.vmap", "jax.pmap", "jax.checkpoint", "jax.grad",
    "jax.value_and_grad", "jax.lax.scan", "jax.lax.map",
    "jax.lax.while_loop", "jax.lax.fori_loop", "jax.lax.cond",
    "jax.experimental.pallas.pallas_call",
    "jax.shard_map",
}
# Also accepted unnormalized (conventional aliases), so fixture modules
# and unusual import spellings still root correctly.
_ALIAS_WRAPPERS = {"jit", "vmap", "pallas_call", "shard_map", "scan"}


@dataclasses.dataclass
class CallSite:
    node: ast.Call
    norm: str | None       # normalized dotted target ("time.time"), if any
    fid: str | None        # resolved project function id, if any
    host_only: bool        # lexically fenced behind a trace-guard raise


@dataclasses.dataclass
class FuncInfo:
    fid: str               # "module.name:qualname"
    module: Module
    qualname: str
    node: ast.AST          # FunctionDef / AsyncFunctionDef / Lambda
    calls: list[CallSite] = dataclasses.field(default_factory=list)
    traced_root: str | None = None   # why this function roots a trace


def _is_trace_guard(stmt: ast.stmt) -> bool:
    """``if <trace check>: raise ...`` — the ops-contract fence."""
    if not isinstance(stmt, ast.If):
        return False
    if not any(isinstance(s, ast.Raise) for s in stmt.body):
        return False
    for node in ast.walk(stmt.test):
        if isinstance(node, ast.Call):
            name = dotted(node.func) or ""
            if "traced" in name.split(".")[-1].lower():
                return True
            if name.endswith("isinstance") or name == "isinstance":
                tail = node.args[1] if len(node.args) > 1 else None
                if tail is not None and "Tracer" in ast.dump(tail):
                    return True
    return False


class CallGraph:
    def __init__(self, modules: dict[str, Module]):
        self.modules = modules
        self.functions: dict[str, FuncInfo] = {}
        self._module_scope: dict[str, dict[str, str]] = {}  # mod -> name->fid
        self._imports: dict[str, dict[str, str]] = {}
        for mod in modules.values():
            self._imports[mod.name] = import_map(mod.tree)
            self._collect(mod)
        for mod in modules.values():
            self._link(mod)

    # -- pass 1: enumerate functions ----------------------------------
    def _collect(self, mod: Module) -> None:
        scope: dict[str, str] = {}
        self._module_scope[mod.name] = scope

        def walk(node: ast.AST, prefix: str) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    qual = f"{prefix}{child.name}"
                    fid = f"{mod.name}:{qual}"
                    self.functions[fid] = FuncInfo(fid, mod, qual, child)
                    if not prefix:
                        scope[child.name] = fid
                    walk(child, qual + ".")
                elif isinstance(child, ast.ClassDef):
                    walk(child, f"{prefix}{child.name}.")
                elif isinstance(child, ast.Lambda):
                    qual = f"{prefix}<lambda@{child.lineno}>"
                    fid = f"{mod.name}:{qual}"
                    self.functions[fid] = FuncInfo(fid, mod, qual, child)
                    walk(child, qual + ".")
                else:
                    walk(child, prefix)

        walk(mod.tree, "")

    # -- name resolution ----------------------------------------------
    def _resolve_module(self, here: str, target: str) -> str:
        """Resolve a possibly-relative dotted module path."""
        if not target.startswith("."):
            return target
        level = len(target) - len(target.lstrip("."))
        base = here.split(".")
        # a module's imports resolve against its package
        base = base[:-1] if len(base) >= level else []
        base = base[: len(base) - (level - 1)] if level > 1 else base
        rest = target.lstrip(".")
        return ".".join(base + ([rest] if rest else []))

    def _resolve_name(self, mod: Module, scope_chain: list[str],
                      name: str) -> tuple[str | None, str | None]:
        """A bare name -> (project fid, normalized dotted), best effort."""
        for outer in reversed(scope_chain):
            fid = f"{mod.name}:{outer}.{name}" if outer else None
            if fid and fid in self.functions:
                return fid, None
        fid = self._module_scope[mod.name].get(name)
        if fid:
            return fid, None
        origin = self._imports[mod.name].get(name)
        if origin:
            origin = self._resolve_module(mod.name, origin)
            head, _, tail = origin.rpartition(".")
            if head in self.modules and f"{head}:{tail}" in self.functions:
                return f"{head}:{tail}", origin
            return None, origin
        return None, name    # builtin / unknown global

    def _resolve_call(self, mod: Module, scope_chain: list[str],
                      call: ast.Call) -> tuple[str | None, str | None]:
        name = dotted(call.func)
        if name is None:
            return None, None
        if "." not in name:
            return self._resolve_name(mod, scope_chain, name)
        root, _, rest = name.partition(".")
        origin = self._imports[mod.name].get(root)
        if origin is None:
            return None, name            # e.g. self.x(), obj.m()
        origin = self._resolve_module(mod.name, origin)
        norm = f"{origin}.{rest}"
        if origin in self.modules:
            head, _, tail = norm.rpartition(".")
            if head in self.modules and f"{head}:{tail}" in self.functions:
                return f"{head}:{tail}", norm
        return None, norm

    # -- pass 2: edges + traced roots ---------------------------------
    def _link(self, mod: Module) -> None:
        graph = self

        def func_of(scope_chain: list[str]) -> FuncInfo | None:
            if not scope_chain:
                return None
            return graph.functions.get(f"{mod.name}:{scope_chain[-1]}")

        def handle_call(call: ast.Call, scope_chain: list[str],
                        host_only: bool) -> None:
            fid, norm = graph._resolve_call(mod, scope_chain, call)
            info = func_of(scope_chain)
            if info is not None:
                info.calls.append(CallSite(call, norm, fid, host_only))
            # does this call enter a trace with a callable argument?
            wrapper = norm or (dotted(call.func) or "")
            short = wrapper.split(".")[-1]
            if wrapper in TRACE_WRAPPERS or short in _ALIAS_WRAPPERS:
                for arg in call.args[:1]:
                    graph._root_arg(mod, scope_chain, arg,
                                    f"passed to {wrapper or short}()")
                for kw in call.keywords:
                    if kw.arg in ("f", "fun", "func", "body_fun", "kernel"):
                        graph._root_arg(mod, scope_chain, kw.value,
                                        f"passed to {wrapper or short}()")
            # functools.partial(jax.jit, ...) used as a decorator factory
            if short == "partial" and call.args:
                inner = dotted(call.args[0])
                if inner:
                    _, inner_norm = graph._resolve_call(
                        mod, scope_chain,
                        ast.Call(func=call.args[0], args=[], keywords=[]))
                    if (inner_norm or inner) in TRACE_WRAPPERS:
                        for arg in call.args[1:2]:
                            graph._root_arg(mod, scope_chain, arg,
                                            f"partial({inner})")

        def visit_block(stmts: list[ast.stmt], scope_chain: list[str],
                        host_only: bool) -> None:
            fenced = host_only
            for stmt in stmts:
                visit_node(stmt, scope_chain, fenced)
                if _is_trace_guard(stmt):
                    fenced = True

        def visit_node(node: ast.AST, scope_chain: list[str],
                       host_only: bool) -> None:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = (f"{scope_chain[-1]}.{node.name}" if scope_chain
                        else node.name)
                info = graph.functions[f"{mod.name}:{qual}"]
                graph._apply_decorators(mod, scope_chain, info)
                for dec in node.decorator_list:
                    visit_node(dec, scope_chain, host_only)
                visit_block(node.body, scope_chain + [qual], False)
                return
            if isinstance(node, ast.ClassDef):
                qual = (f"{scope_chain[-1]}.{node.name}" if scope_chain
                        else node.name)
                # method qualnames nest under the class, not the function
                visit_block(node.body, scope_chain[:-1] + [qual]
                            if scope_chain else [qual], host_only)
                return
            if isinstance(node, ast.Lambda):
                qual = (f"{scope_chain[-1]}.<lambda@{node.lineno}>"
                        if scope_chain else f"<lambda@{node.lineno}>")
                visit_node(node.body, scope_chain + [qual], host_only)
                return
            if isinstance(node, ast.Call):
                handle_call(node, scope_chain, host_only)
            for stmt_field in ("body", "orelse", "finalbody"):
                block = getattr(node, stmt_field, None)
                if (isinstance(block, list) and block
                        and isinstance(block[0], ast.stmt)):
                    visit_block(block, scope_chain, host_only)
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.stmt):
                    continue               # handled by the block visitor
                visit_node(child, scope_chain, host_only)

        visit_block(mod.tree.body, [], False)

    def _apply_decorators(self, mod: Module, scope_chain: list[str],
                          info: FuncInfo) -> None:
        for dec in info.node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            name = dotted(target) or ""
            _, norm = self._resolve_call(
                mod, scope_chain,
                ast.Call(func=target, args=[], keywords=[])) \
                if name else (None, None)
            full = norm or name
            short = full.split(".")[-1]
            if full in TRACE_WRAPPERS or short in _ALIAS_WRAPPERS:
                info.traced_root = f"decorated @{full or short}"
            elif short == "partial" and isinstance(dec, ast.Call) \
                    and dec.args:
                inner = dotted(dec.args[0]) or ""
                _, inner_norm = self._resolve_call(
                    mod, scope_chain,
                    ast.Call(func=dec.args[0], args=[], keywords=[]))
                if (inner_norm or inner) in TRACE_WRAPPERS:
                    info.traced_root = f"decorated @partial({inner})"

    def _root_arg(self, mod: Module, scope_chain: list[str],
                  arg: ast.expr, why: str) -> None:
        if isinstance(arg, ast.Lambda):
            qual = (f"{scope_chain[-1]}.<lambda@{arg.lineno}>"
                    if scope_chain else f"<lambda@{arg.lineno}>")
            info = self.functions.get(f"{mod.name}:{qual}")
            if info is not None and info.traced_root is None:
                info.traced_root = why
            return
        name = dotted(arg)
        if name is None:
            return
        if "." in name:
            fid, _ = self._resolve_call(
                mod, scope_chain, ast.Call(func=arg, args=[], keywords=[]))
        else:
            fid, _ = self._resolve_name(mod, scope_chain, name)
        if fid is not None:
            info = self.functions[fid]
            if info.traced_root is None:
                info.traced_root = why

    # -- reachability ---------------------------------------------------
    def traced_reachable(self) -> dict[str, str]:
        """fid -> provenance string ("root: ..." or "via <caller fid>")
        for every function a traced execution can reach.  Host-only
        (guard-fenced) call sites do not extend reachability."""
        frontier = [(fid, f"root: {info.traced_root}")
                    for fid, info in self.functions.items()
                    if info.traced_root is not None]
        seen: dict[str, str] = {}
        while frontier:
            fid, why = frontier.pop()
            if fid in seen:
                continue
            seen[fid] = why
            for site in self.functions[fid].calls:
                if site.host_only or site.fid is None:
                    continue
                if site.fid not in seen:
                    frontier.append((site.fid, f"via {fid}"))
        return seen
