"""tracer-purity: traced functions must be pure.

TRACE001 — a function handed to ``jax.jit`` / ``shard_map`` / ``pmap`` /
``map_batches`` / ``map_reduce`` (or installed as a fusion ``emit=``
tracer) calls ``time.*``, ``random.*``, telemetry, acquires a lock, or
does I/O. Side effects inside a tracer run once at trace time and then
silently never again — a wall-clock read or a meter increment there is
a bug every time, and a lock acquire can deadlock the compile path.

``arr.at[i].set(v)`` is functional jax, not telemetry — ``.set`` is
deliberately NOT in the impurity list.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Tuple

from ..astutil import (FuncNode, call_name, dotted_name, index_functions,
                       module_level_defs)
from ..core import Context, Finding

RULES = {
    "TRACE001": "impure operation inside a traced/jitted function",
}

#: call names whose first positional argument is traced
TRACING_CALLS = {
    "jax.jit", "jit", "jax.pmap", "pmap", "shard_map",
    "jax.shard_map",
}

#: attribute/bare suffixes whose first argument is traced (methods too)
TRACING_SUFFIXES = {"map_batches", "map_reduce", "distributed_map_reduce"}

_RANDOM_PREFIXES = ("random.", "np.random.", "numpy.random.")


def _impure_reason(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Call):
        name = call_name(node)
        if not name:
            return None
        if name.startswith("time."):
            return f"wall-clock/sleep call {name}()"
        if name.startswith(_RANDOM_PREFIXES) and not name.endswith(".Random"):
            return f"unseeded RNG call {name}()"
        if name.startswith("telemetry.") or name.endswith(
                (".inc", ".observe", ".labels")):
            return f"telemetry call {name}()"
        if name == "Span" or name.endswith(".Span"):
            return f"telemetry span {name}()"
        if name.endswith(".acquire"):
            return f"lock acquire {name}()"
        if name in ("open", "print"):
            return f"I/O call {name}()"
        if name.endswith((".sendall", ".recv", ".connect")):
            return f"socket I/O {name}()"
    elif isinstance(node, ast.With):
        for item in node.items:
            nm = dotted_name(item.context_expr) or ""
            if "lock" in nm.lower():
                return f"holds lock {nm}"
    return None


def _is_tracing_decorator(dec: ast.expr) -> Optional[str]:
    name = dotted_name(dec)
    if name in TRACING_CALLS:
        return name
    if isinstance(dec, ast.Call):
        cname = call_name(dec) or ""
        if cname in TRACING_CALLS:
            return cname
        if cname in ("partial", "functools.partial") and dec.args:
            inner = dotted_name(dec.args[0])
            if inner in TRACING_CALLS:
                return inner
    return None


def run(ctx: Context) -> List[Finding]:
    findings: List[Finding] = []
    markers = ("jit", "shard_map", "pmap", "map_batches", "map_reduce",
               "emit")
    for mod in ctx.modules:
        # fast gate: no tracing entry point named anywhere → nothing
        # can be traced in this module
        if not any(m in mod.source for m in markers):
            continue
        funcs = index_functions(mod.tree)
        top = module_level_defs(mod.tree)
        by_simple: Dict[str, List[ast.AST]] = {}
        for qual, info in funcs.items():
            by_simple.setdefault(qual.split(".")[-1], []).append(info.node)

        traced: List[Tuple[ast.AST, str, str]] = []  # node, symbol, how

        def resolve(arg: ast.expr, how: str) -> None:
            if isinstance(arg, ast.Lambda):
                traced.append((arg, "<lambda>", how))
            elif isinstance(arg, ast.Name):
                node = top.get(arg.id)
                if node is None:
                    cands = by_simple.get(arg.id, [])
                    node = cands[0] if len(cands) == 1 else None
                if node is not None:
                    traced.append((node, arg.id, how))

        for qual, info in funcs.items():
            for dec in info.node.decorator_list:
                how = _is_tracing_decorator(dec)
                if how:
                    traced.append(
                        (info.node, qual, f"decorated with @{how}"))
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node) or ""
            last = name.split(".")[-1]
            if (name in TRACING_CALLS or last in TRACING_SUFFIXES) \
                    and node.args:
                resolve(node.args[0], f"passed to {name}()")
            for kw in node.keywords:
                if kw.arg == "emit" and kw.value is not None:
                    resolve(kw.value, "installed as fusion emit= tracer")

        seen = set()
        for fn_node, symbol, how in traced:
            key = id(fn_node)
            if key in seen:
                continue
            seen.add(key)
            body = fn_node.body if isinstance(fn_node, FuncNode) \
                else [fn_node.body]
            for stmt in body:
                for sub in ast.walk(stmt) if isinstance(stmt, ast.AST) \
                        else ():
                    reason = _impure_reason(sub)
                    if reason:
                        findings.append(Finding(
                            rule="TRACE001", file=mod.rel,
                            line=getattr(sub, "lineno", fn_node.lineno),
                            symbol=symbol,
                            message=f"{reason} inside traced function "
                                    f"({how})",
                            snippet=mod.line_text(
                                getattr(sub, "lineno", fn_node.lineno))))
    return findings
