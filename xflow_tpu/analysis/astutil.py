"""Small AST helpers shared by the xflowlint passes."""

from __future__ import annotations

import ast
import os
from typing import Iterator, Optional


def engine_modules() -> dict:
    """{engine name: builder module (repo-relative)} — the literal
    `ENGINE_MODULES` of xflow_tpu/train/engine.py, the one place that
    lists the table engines. Read from its source: the AST tier never
    imports the code it checks (engine.py imports jax)."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "train", "engine.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "ENGINE_MODULES"
                for t in node.targets):
            return ast.literal_eval(node.value)
    raise LookupError(f"no ENGINE_MODULES literal in {path}")


def dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, else None. `self.x.y`
    renders as 'self.x.y'; calls/subscripts break the chain."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    elif isinstance(node, ast.Attribute):  # unreachable, kept for clarity
        return None
    else:
        return None
    return ".".join(reversed(parts))


def call_name(call: ast.Call) -> Optional[str]:
    """Dotted name of a call's callee ('jax.jit', 'print', ...)."""
    return dotted(call.func)


def walk_scope(node: ast.AST) -> Iterator[ast.AST]:
    """Like ast.walk over a function body, but does NOT descend into
    nested function/class definitions (they are separate scopes the
    call-graph handles explicitly)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        child = stack.pop()
        yield child
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(child))


def parent_map(tree: ast.AST) -> dict:
    """child node -> parent node, for lexical-context questions."""
    parents: dict = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


def enclosing(node: ast.AST, parents: dict, kinds: tuple) -> Optional[ast.AST]:
    """Nearest ancestor of one of `kinds` (or None)."""
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, kinds):
            return cur
        cur = parents.get(cur)
    return None


def in_loop(node: ast.AST, parents: dict, stop_at: tuple = ()) -> bool:
    """Whether `node` sits inside a for/while body, without crossing a
    function boundary (a loop in an outer function does not count)."""
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, (ast.For, ast.AsyncFor, ast.While)):
            return True
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda) + stop_at):
            return False
        cur = parents.get(cur)
    return False


def import_aliases(tree: ast.AST) -> dict:
    """local name -> canonical dotted origin, from import statements:
    `import numpy as np` -> {np: numpy}; `import jax.numpy as jnp` ->
    {jnp: jax.numpy}; `from time import perf_counter as pc` ->
    {pc: time.perf_counter}. Lets rule tables match canonical names
    (`time.perf_counter`) whatever the module imported them as."""
    amap: dict = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                local = a.asname or a.name.split(".")[0]
                amap[local] = a.name if a.asname else a.name.split(".")[0]
        elif isinstance(node, ast.ImportFrom) and node.module \
                and node.level == 0:
            for a in node.names:
                if a.name == "*":
                    continue
                amap[a.asname or a.name] = f"{node.module}.{a.name}"
    return amap


def canonical(name: Optional[str], aliases: dict) -> Optional[str]:
    """Rewrite a dotted name's first component through the import-alias
    map ('np.random.seed' -> 'numpy.random.seed')."""
    if not name:
        return name
    head, _, rest = name.partition(".")
    origin = aliases.get(head)
    if origin is None or origin == head:
        return name
    return f"{origin}.{rest}" if rest else origin


def const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def defs_by_name(defs: list) -> dict:
    """simple name -> [qualnames] over a func_defs() list."""
    by_name: dict = {}
    for qn, _node, _cls in defs:
        by_name.setdefault(qn.split(".")[-1], []).append(qn)
    return by_name


def resolve_scoped(simple: str, caller_qn: str, by_name: dict) -> list:
    """Scope-aware name resolution: among same-named definitions, pick
    the ones whose defining scope is an ancestor of the caller's scope,
    preferring the innermost (two `def one(...)` in different functions
    must never cross-link — that is how a host helper would get marked
    jit-reachable). Falls back to every candidate for `self.x` refs."""
    cands = by_name.get(simple, [])
    if len(cands) <= 1:
        return list(cands)
    visible = []
    for c in cands:
        scope = c.rsplit(".", 1)[0] if "." in c else ""
        if scope == "" or caller_qn == scope or caller_qn.startswith(
                scope + "."):
            visible.append((len(scope.split(".")) if scope else 0, c))
    if not visible:
        return list(cands)
    best = max(d for d, _c in visible)
    return [c for d, c in visible if d == best]


def scope_sites(tree: ast.AST, defs: list):
    """Yields (caller qualname, node) for every node, attributed to its
    innermost enclosing function ('' = module level)."""
    covered: dict = {}
    for qn, node, _cls in defs:
        for sub in walk_scope(node):
            covered.setdefault(id(sub), (qn, sub))
    # module-level statements (not inside any def)
    seen_ids = set(covered)
    for node in ast.walk(tree):
        if id(node) not in seen_ids and not isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            covered.setdefault(id(node), ("", node))
    return covered.values()


def local_call_graph(defs: list) -> dict:
    """qualname -> set of callee qualnames (module-local, scope-aware:
    a call binds to the innermost visible same-named definition)."""
    by_name = defs_by_name(defs)
    graph: dict = {}
    for qn, node, _cls in defs:
        callees: set = set()
        for sub in walk_scope(node):
            if not isinstance(sub, ast.Call):
                continue
            cn = call_name(sub)
            if cn is None:
                continue
            simple = cn.split(".")[-1]
            if cn == simple or cn == f"self.{simple}" or cn == f"cls.{simple}":
                callees.update(resolve_scoped(simple, qn, by_name))
        graph[qn] = callees
    return graph


def reachable(roots: set, graph: dict) -> set:
    seen = set(roots)
    stack = list(roots)
    while stack:
        cur = stack.pop()
        for nxt in graph.get(cur, ()):
            if nxt not in seen:
                seen.add(nxt)
                stack.append(nxt)
    return seen


def func_defs(tree: ast.AST) -> list:
    """Every (qualname, node, class_name) function/method in a module.
    Qualnames use '.' ('Cls.method', 'outer.inner')."""
    out = []

    def visit(node: ast.AST, prefix: str, cls: Optional[str]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qn = f"{prefix}{child.name}"
                out.append((qn, child, cls))
                visit(child, qn + ".", cls)
            elif isinstance(child, ast.ClassDef):
                visit(child, f"{prefix}{child.name}.", child.name)
            else:
                visit(child, prefix, cls)

    visit(tree, "", None)
    return out
