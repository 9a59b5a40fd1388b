"""Trace-purity pass: no host-side effects reachable from traced code.

Consumes the `repro.analysis.callgraph` graph: every function reachable
from a traced root (``jax.jit`` / ``vmap`` / ``lax.scan`` /
``pallas_call`` region) is checked for

  * **host-call** — wall clock (``time.*``), host RNG (stdlib
    ``random.*``, ``numpy.random.*``), console / filesystem
    (``print`` / ``input`` / ``breakpoint`` / ``open``), environment
    (``os.environ`` / ``os.getenv``), profiler spans
    (``jax.profiler.*``, ``repro.telemetry.spans.trace_span``: host
    annotations that a trace would open once, at trace time), device
    sync (``.item()``, and
    ``float()`` / ``int()`` wrapped directly around an array-producing
    call) — all of which either crash under a tracer or silently bake a
    trace-time value into the compiled program;
  * **inplace-store** — ``x[i] = v`` / ``x[i] += v`` subscript stores
    (JAX arrays need ``x.at[i].set(v)``; a store that *works* under a
    trace is mutating host state, a retrace-count hazard);
  * **set-iteration** — iterating a set (literal or ``set(...)``) in
    traced code, where Python's unordered iteration makes trace
    structure run-to-run nondeterministic;
  * **host-guard** — the `kernels/*/ops.py` dispatch contract from
    `docs/kernels.md`: every call into a host engine module
    (``frontier`` / ``oracle``) must sit *behind* a raising
    ``if _traced(...)`` fence.

Statements lexically after such a fence are host-only and exempt (see
`callgraph` for the pruning rule).
"""
from __future__ import annotations

import ast

from repro.analysis.callgraph import CallGraph, FuncInfo, _is_trace_guard
from repro.analysis.core import Finding, Module, dotted

# Normalized dotted prefixes that are host-side effects under a trace.
_HOST_PREFIXES = (
    "time.", "random.", "numpy.random.", "os.environ", "os.getenv",
    "os.urandom", "os.system", "subprocess.", "socket.",
    "jax.profiler.", "repro.telemetry.",
)
_HOST_BUILTINS = {"print", "input", "breakpoint", "open"}
# float(jnp.sum(x)) / int(lax.argmax(...)) force a device sync and bake
# the traced value into a Python scalar.  Plain numpy is deliberately
# absent: int(np.ceil(...)) over static shapes is trace-time constant
# math, not a sync.
_ARRAY_PRODUCERS = ("jax.numpy.", "jnp.", "jax.lax.", "lax.", "jax.")
# Host engine modules under kernels/*: calls into them from an ops
# dispatcher must be fenced by a raising trace check.
_HOST_ENGINE_MODULES = {"frontier", "oracle", "host", "bfs"}


def _short(fid: str) -> str:
    mod, _, qual = fid.partition(":")
    return f"{mod.rsplit('.', 1)[-1]}.{qual}"


def _call_findings(info: FuncInfo, why: str) -> list[Finding]:
    out: list[Finding] = []
    for site in info.calls:
        if site.host_only:
            continue
        raw = dotted(site.node.func) or ""
        norm = site.norm or raw
        hit = None
        if norm in _HOST_BUILTINS:
            hit = f"{norm}()"
        elif norm.startswith(_HOST_PREFIXES):
            hit = f"{norm}()"
        elif raw.endswith(".item") and site.fid is None:
            hit = ".item()"
        elif norm in ("float", "int", "bool") and site.node.args:
            arg = site.node.args[0]
            if isinstance(arg, ast.Call):
                inner = dotted(arg.func) or ""
                if inner.startswith(_ARRAY_PRODUCERS):
                    hit = f"{norm}({inner}(...))"
        if hit is not None:
            out.append(Finding(
                "host-call", info.module.rel, site.node.lineno,
                f"{hit} in {_short(info.fid)}, reachable from traced "
                f"code ({why})"))
    return out


def _body_findings(info: FuncInfo, why: str) -> list[Finding]:
    """inplace-store / set-iteration inside one reachable function,
    honouring trace-guard fencing; nested defs are their own units."""
    out: list[Finding] = []
    node = info.node
    if isinstance(node, ast.Lambda):
        return out
    # Pallas kernels *must* write through their Ref params
    # (``o_ref[...] = x`` is the output idiom, not a host mutation).
    ref_params: set[str] = set()
    if info.traced_root and "pallas_call" in info.traced_root:
        ref_params = {a.arg for a in node.args.args}

    def visit_block(stmts: list[ast.stmt], fenced: bool) -> None:
        for stmt in stmts:
            if not fenced:
                check_stmt(stmt)
            visit_children(stmt, fenced)
            if _is_trace_guard(stmt):
                fenced = True

    def visit_children(node: ast.AST, fenced: bool) -> None:
        for field in ("body", "orelse", "finalbody"):
            block = getattr(node, field, None)
            if (isinstance(block, list) and block
                    and isinstance(block[0], ast.stmt)):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.Lambda)):
                    continue           # separate function unit
                visit_block(block, fenced)
        for h in getattr(node, "handlers", ()):
            visit_block(h.body, fenced)

    def check_stmt(stmt: ast.stmt) -> None:
        targets = []
        if isinstance(stmt, ast.Assign):
            targets = stmt.targets
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
            targets = [stmt.target]
        for t in targets:
            for sub in ast.walk(t):
                if not isinstance(sub, ast.Subscript):
                    continue
                base = dotted(sub.value) or "<expr>"
                if base in ref_params:
                    continue          # pallas Ref store idiom
                # d["k"] = v builds a host dict (params pytrees are
                # assembled this way at trace time — deterministic);
                # d["k"] += v is read-modify-write of live host state.
                if (isinstance(stmt, ast.Assign)
                        and isinstance(sub.slice, ast.Constant)
                        and isinstance(sub.slice.value, str)):
                    continue
                out.append(Finding(
                    "inplace-store", info.module.rel, stmt.lineno,
                    f"subscript store {base}[...] in "
                    f"{_short(info.fid)}, reachable from traced code "
                    f"({why}); use .at[].set() for arrays"))
        for it in _iter_exprs(stmt):
            if isinstance(it, ast.Set) or (
                    isinstance(it, ast.Call)
                    and dotted(it.func) in ("set", "frozenset")):
                out.append(Finding(
                    "set-iteration", info.module.rel, it.lineno,
                    f"iteration over an unordered set in "
                    f"{_short(info.fid)}, reachable from traced code "
                    f"({why}); sort it for a stable trace"))

    def _iter_exprs(stmt: ast.stmt):
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            yield stmt.iter
        for sub in ast.walk(stmt):
            if isinstance(sub, ast.comprehension):
                yield sub.iter

    visit_block(node.body, False)
    return out


def _host_guard_findings(graph: CallGraph, mod: Module) -> list[Finding]:
    """Enforce the ops dispatch contract in `repro.kernels.*.ops`."""
    out: list[Finding] = []
    if not (mod.name.startswith("repro.kernels.")
            and mod.name.endswith(".ops")):
        return out
    for info in graph.functions.values():
        if info.module is not mod:
            continue
        for site in info.calls:
            target = site.norm or ""
            if site.fid:
                target = site.fid.partition(":")[0]
            owner = target.rpartition(".")[0] if site.fid is None \
                else target
            parts = owner.split(".")
            if not parts or parts[-1] not in _HOST_ENGINE_MODULES:
                continue
            if not site.host_only:
                callee = dotted(site.node.func) or target
                out.append(Finding(
                    "host-guard", mod.rel, site.node.lineno,
                    f"host engine call {callee}() in {_short(info.fid)} "
                    f"is not behind a raising 'if _traced(...)' check "
                    f"(ops dispatch contract, docs/kernels.md)"))
    return out


def run(modules: dict[str, Module],
        graph: CallGraph | None = None) -> list[Finding]:
    graph = graph or CallGraph(modules)
    findings: list[Finding] = []
    for fid, why in sorted(graph.traced_reachable().items()):
        info = graph.functions[fid]
        findings.extend(_call_findings(info, why))
        findings.extend(_body_findings(info, why))
    for mod in modules.values():
        findings.extend(_host_guard_findings(graph, mod))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings
