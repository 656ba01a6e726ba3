"""G1: jit-purity / tracer-hazard analysis.

A Python side effect inside a traced function is invisible at trace
time and wrong at run time: telemetry `incr()` fires once per
*compile* (not per step), `time.perf_counter()` measures tracing (not
the device), a lock is held for the trace's lifetime, and a host sync
(`.item()`, `block_until_ready`) inside a jitted region stalls the
dispatch queue.  The PR 8 compile sentry catches the recompile
symptom at runtime; this pass catches the cause before anything runs.

Approach (whole-program — hazards increasingly hide one import away
from the `jax.jit` that traces them):

1. index every function/method definition, including nested closures;
2. mark **trace roots**: functions decorated with / passed to a trace
   wrapper (`jax.jit`, `pjit`, `shard_map`, `pallas_call`, `vmap`,
   `grad`, `value_and_grad`, `lax.scan/cond/while_loop/fori_loop`,
   `pmap`, `remat`, `checkify`, ...) — including references to traced
   functions imported from another scanned module;
3. build call edges: direct calls by local name, any function
   reference passed as an argument (covers ``value_and_grad(loss_fn)``
   and scan bodies), and — via ``core.ModuleGraph`` — calls that
   resolve through the import tables into OTHER scanned modules
   (``from ..ops import helper; helper(x)`` inside a jitted step walks
   into ops' `helper`);
4. flag hazard calls in every function reachable from a root,
   reporting each in the file that contains it (suppressions apply
   where the hazard lives, not where the trace root is).

The analysis is deliberately name-based and conservative: dynamic
dispatch (``self.fn(...)``, callables from parameters, ``getattr``)
creates no edges, so a hazard hidden behind one is missed — the price
of zero false edges from host-side driver loops into the traced step
they dispatch.  A call that is itself flagged as a hazard (e.g.
``telemetry.incr``) is a boundary: the graph does not also descend
into the telemetry implementation.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .core import Finding, ModuleGraph, SourceFile

__all__ = ["check_trace_purity", "trace_roots"]

# callables that trace their function argument(s).  Matched against the
# final attribute segment so `jax.jit`, `jax.experimental.pjit.pjit`,
# and a bare `jit` (imported from jax) all resolve.
TRACE_WRAPPERS: Set[str] = {
    "jit", "pjit", "pmap", "shard_map", "pallas_call", "vmap", "grad",
    "value_and_grad", "scan", "cond", "while_loop", "fori_loop",
    "associative_scan", "remat", "checkpoint", "custom_vjp",
    "custom_jvp", "checkify",
}

# telemetry / fault-machinery entry points: any of these inside a trace
# records per-compile, not per-step (or takes a host lock mid-trace)
_TELEMETRY_FNS = {"incr", "gauge", "histogram", "span", "phase",
                  "record_span", "log_verb", "fault_point",
                  "device_annotation",
                  "counters", "reset_counters"}

_HOST_SYNC_METHODS = {"item", "block_until_ready"}


def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _ModuleIndex(ast.NodeVisitor):
    """Functions by (non-qualified) name, plus module import aliases."""

    def __init__(self):
        self.functions: Dict[str, List[ast.AST]] = {}
        self.aliases: Dict[str, str] = {}   # local name -> module path
        self.from_imports: Dict[str, str] = {}  # local name -> source mod

    def visit_Import(self, node: ast.Import):
        for a in node.names:
            self.aliases[(a.asname or a.name).split(".")[0]] = a.name

    def visit_ImportFrom(self, node: ast.ImportFrom):
        mod = node.module or ""
        for a in node.names:
            if a.name != "*":
                self.from_imports[a.asname or a.name] = mod

    def visit_FunctionDef(self, node):
        self.functions.setdefault(node.name, []).append(node)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef


def _is_trace_wrapper(call_func: ast.AST, idx: _ModuleIndex) -> bool:
    dotted = _dotted(call_func)
    if dotted is None:
        return False
    tail = dotted.rsplit(".", 1)[-1]
    if tail not in TRACE_WRAPPERS:
        return False
    head = dotted.split(".", 1)[0]
    if "." in dotted:
        # attribute form: head must be a jax-ish module alias (jax,
        # jax.numpy won't carry these names; pl for pallas, lax, ...)
        src = idx.aliases.get(head, "") or idx.from_imports.get(head, "")
        return ("jax" in src or head in ("jax", "lax", "pl", "pjit")
                or "pallas" in src)
    # bare name: must have been imported from a jax module
    src = idx.from_imports.get(dotted, "")
    return "jax" in src or "pallas" in src


def _fn_args_of_call(call: ast.Call) -> List[str]:
    """Names passed as positional/keyword args (candidate traced fns)."""
    out = []
    for a in list(call.args) + [k.value for k in call.keywords]:
        if isinstance(a, ast.Name):
            out.append(a.id)
    return out


def trace_roots(sf: SourceFile, idx: _ModuleIndex) -> Set[ast.AST]:
    """Function nodes handed to (or decorated by) a trace wrapper."""
    roots: Set[ast.AST] = set()
    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                if _is_trace_wrapper(target, idx):
                    roots.add(node)
                # @partial(jax.jit, ...) / @functools.partial(jit, ...)
                if (isinstance(dec, ast.Call)
                        and (_dotted(dec.func) or "").rsplit(".", 1)[-1]
                        == "partial" and dec.args
                        and _is_trace_wrapper(dec.args[0], idx)):
                    roots.add(node)
        elif isinstance(node, ast.Call) and _is_trace_wrapper(node.func,
                                                              idx):
            for name in _fn_args_of_call(node):
                for fn in idx.functions.get(name, ()):
                    roots.add(fn)
    return roots


_Node = Tuple[SourceFile, ast.AST]


def _resolved_fn(graph: Optional[ModuleGraph], sf: SourceFile,
                 dotted: str) -> Optional[_Node]:
    """(file, def) when `dotted` statically resolves to a top-level
    function in another scanned module."""
    if graph is None:
        return None
    hit = graph.resolve(sf, dotted)
    if hit is None:
        return None
    target_sf, node, _mod = hit
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return (target_sf, node)
    return None


def _call_edges(sf: SourceFile, fn: ast.AST, idx: _ModuleIndex,
                graph: Optional[ModuleGraph]) -> Set[_Node]:
    """Callees of `fn`: direct calls by local name, function references
    passed as arguments (higher-order: grad/scan bodies), and calls
    resolving through the import tables into other scanned modules.
    Hazard calls are boundaries — flagged at the call site, not
    descended into."""
    out: Set[_Node] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        names = set(_fn_args_of_call(node))
        dotted = _dotted(node.func)
        if dotted is not None and _hazard(node, idx) is None:
            names.add(dotted)
        for name in names:
            if "." not in name and name in idx.functions:
                for callee in idx.functions[name]:
                    if callee is not fn:
                        out.add((sf, callee))
                continue
            hit = _resolved_fn(graph, sf, name)
            if hit is not None and hit[1] is not fn:
                out.add(hit)
    return out


def _hazard(call: ast.Call, idx: _ModuleIndex) -> Optional[Tuple[str, str, str]]:
    """(rule, message, hint) when this call is a tracer hazard."""
    dotted = _dotted(call.func)
    if dotted is None:
        # method call on an expression: x.item(), y.block_until_ready()
        if isinstance(call.func, ast.Attribute) \
                and call.func.attr in _HOST_SYNC_METHODS:
            return ("G106",
                    f".{call.func.attr}() forces a host sync on a "
                    f"traced value",
                    "return the array and sync in the host loop")
        return None
    head, _, _rest = dotted.partition(".")
    tail = dotted.rsplit(".", 1)[-1]
    src_mod = idx.aliases.get(head, "") or idx.from_imports.get(head, "")

    if tail in _HOST_SYNC_METHODS or dotted.endswith("device_get"):
        return ("G106", f"{dotted}() forces a host sync on a traced "
                        f"value",
                "return the array and sync in the host loop")
    if head == "time" and src_mod == "time":
        return ("G102", f"{dotted}() measures trace time, not device "
                        f"time, inside a traced function",
                "time around the jitted call with block_until_ready")
    if (head == "random" and src_mod == "random") or \
            (".random." in f"{dotted}." and src_mod == "numpy"):
        return ("G103", f"{dotted}() draws host randomness inside a "
                        f"traced function (baked in at trace time)",
                "thread a jax.random key through the function")
    if head == "print":
        return ("G104", "print() inside a traced function fires at "
                        "trace time only",
                "use jax.debug.print for runtime values")
    if tail == "acquire" or (tail in ("Lock", "RLock")
                             and src_mod == "threading"):
        return ("G105", f"{dotted}() acquires a host lock inside a "
                        f"traced function",
                "hoist locking out of the traced region")
    # telemetry: module-attribute form (telemetry.incr / core_telemetry
    # .span) or a bare name imported from a telemetry module
    if tail in _TELEMETRY_FNS:
        if "telemetry" in head or "telemetry" in src_mod \
                or "faults" in src_mod:
            return ("G101", f"{dotted}() records host telemetry inside "
                            f"a traced function (fires per compile, "
                            f"not per step)",
                    "record from the host loop around the jitted call")
    return None


def _scan_fn(sf: SourceFile, fn: ast.AST, idx: _ModuleIndex,
             findings: List[Finding], seen_lines: Set[int]) -> None:
    # skip nested function definitions: they are separate graph nodes,
    # reachable (and scanned) only if an edge leads to them
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if isinstance(node, ast.With):
            for item in node.items:
                d = _dotted(item.context_expr) or ""
                if d.split(".")[-1].lower().endswith("lock") \
                        and node.lineno not in seen_lines:
                    seen_lines.add(node.lineno)
                    if not sf.suppressed("G105", node.lineno):
                        findings.append(sf.finding(
                            "G105", node.lineno,
                            f"'with {d}' holds a host lock inside "
                            f"traced function {getattr(fn, 'name', '?')}",
                            hint="hoist locking out of the traced "
                                 "region"))
        if isinstance(node, ast.Call):
            hz = _hazard(node, idx)
            if hz is not None and node.lineno not in seen_lines:
                rule, msg, hint = hz
                seen_lines.add(node.lineno)
                if not sf.suppressed(rule, node.lineno):
                    findings.append(sf.finding(
                        rule, node.lineno,
                        f"{msg} (reachable from a trace root via "
                        f"{getattr(fn, 'name', '?')})", hint=hint))
        stack.extend(ast.iter_child_nodes(node))


def _imported_roots(sf: SourceFile, idx: _ModuleIndex,
                    graph: Optional[ModuleGraph]) -> Set[_Node]:
    """Functions defined in OTHER scanned modules but handed to a trace
    wrapper here: ``jax.jit(imported_step)``."""
    roots: Set[_Node] = set()
    if graph is None:
        return roots
    for node in ast.walk(sf.tree):
        if not (isinstance(node, ast.Call)
                and _is_trace_wrapper(node.func, idx)):
            continue
        for name in _fn_args_of_call(node):
            if name in idx.functions:
                continue  # local — trace_roots already has it
            hit = _resolved_fn(graph, sf, name)
            if hit is not None:
                roots.add(hit)
    return roots


def check_trace_purity(files: Sequence[SourceFile],
                       graph: Optional[ModuleGraph] = None
                       ) -> List[Finding]:
    files = [sf for sf in files if sf.tree is not None]
    if graph is None:
        graph = ModuleGraph(files)
    idxs: Dict[SourceFile, _ModuleIndex] = {}
    for sf in files:
        idx = _ModuleIndex()
        idx.visit(sf.tree)
        idxs[sf] = idx
    roots: Set[_Node] = set()
    for sf in files:
        idx = idxs[sf]
        roots.update((sf, fn) for fn in trace_roots(sf, idx))
        roots.update(_imported_roots(sf, idx, graph))
    # BFS over the interprocedural call graph
    reachable: Set[_Node] = set(roots)
    frontier = list(roots)
    while frontier:
        sf, fn = frontier.pop()
        for callee in _call_edges(sf, fn, idxs[sf], graph):
            if callee not in reachable and callee[0] in idxs:
                reachable.add(callee)
                frontier.append(callee)
    findings: List[Finding] = []
    seen_lines: Dict[SourceFile, Set[int]] = {}
    for sf, fn in sorted(reachable,
                         key=lambda n: (n[0].rel, n[1].lineno)):
        _scan_fn(sf, fn, idxs[sf], findings,
                 seen_lines.setdefault(sf, set()))
    return findings
