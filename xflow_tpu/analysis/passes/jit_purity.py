"""XF101 jit-purity: host-side effects inside traced code.

A function traced by `jax.jit`/`pjit`/`shard_map`/`jax.grad`/a
`lax.scan`/`while_loop`/`cond` body executes ONCE at trace time; a
`time.perf_counter()`, `random.random()`, `print`, file write, or
global mutation inside it runs at compile time and then never again —
the classic silent bug where a "timer" measures tracing, an RNG draw
freezes into the compiled program, and a log line prints once per
compile instead of once per step. PR 2 moved every duration in this
repo to host-side `time.perf_counter` *outside* the step exactly
because of this; this pass enforces it mechanically.

Detection: functions are "jit-reachable" when they are (a) decorated
with a jit-family transform, (b) passed to a jit-family call
(`jax.jit(f)`, `shard_map(f, ...)`, `lax.scan(f, ...)`, ...), or
(c) called (by name, transitively, within the module) from a
jit-reachable function. Calls to the banned host APIs — and `global`
mutations — inside jit-reachable code are findings. `jax.debug.print`
/ `jax.debug.callback` / `jax.random.*` are the sanctioned escape
hatches and never flagged; functions only *referenced* as
`pure_callback`/`io_callback` targets are host code, not jit roots.
"""

from __future__ import annotations

import ast

from xflow_tpu.analysis import astutil
from xflow_tpu.analysis.core import Finding, Project, register_pass

RULE = "XF101"

# callables whose function-valued arguments get traced
JIT_WRAPPERS = {
    "jax.jit", "jit", "pjit", "jax.pjit",
    "shard_map", "jax.shard_map", "jax.experimental.shard_map.shard_map",
    "jax.grad", "jax.value_and_grad", "jax.vmap", "jax.pmap",
    "jax.checkpoint", "jax.remat", "jax.lax.map",
    "jax.lax.scan", "lax.scan",
    "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.fori_loop", "lax.fori_loop",
    "jax.lax.cond", "lax.cond", "jax.lax.switch", "lax.switch",
}

# host-effect calls banned inside traced code: {dotted name: why}
BANNED_CALLS = {
    "time.time": "wall clock freezes at trace time",
    "time.perf_counter": "host timer freezes at trace time (PR 2 rule: "
                         "time steps from the host, outside the program)",
    "time.monotonic": "host timer freezes at trace time",
    "time.process_time": "host timer freezes at trace time",
    "time.sleep": "host sleep runs at trace time only",
    "datetime.now": "wall clock freezes at trace time",
    "datetime.utcnow": "wall clock freezes at trace time",
    "datetime.datetime.now": "wall clock freezes at trace time",
    "datetime.datetime.utcnow": "wall clock freezes at trace time",
    "print": "prints once per COMPILE, not per step (use jax.debug.print)",
    "input": "host IO inside traced code",
    "open": "host IO runs at trace time only",
    "uuid.uuid4": "host RNG freezes at trace time",
    "os.urandom": "host RNG freezes at trace time",
}
# whole host-RNG namespaces (any attribute under them)
BANNED_PREFIXES = {
    "random.": "host RNG freezes into the compiled program "
               "(use jax.random with an explicit key)",
    "np.random.": "numpy RNG freezes into the compiled program "
                  "(use jax.random with an explicit key)",
    "numpy.random.": "numpy RNG freezes into the compiled program "
                     "(use jax.random with an explicit key)",
}
# sanctioned escapes — never flagged even though they look like IO
ALLOWED = {"jax.debug.print", "jax.debug.callback", "jax.debug.breakpoint"}
# function-reference args to these run on the HOST (not jit roots)
HOST_CALLBACK_WRAPPERS = {
    "jax.pure_callback", "jax.experimental.io_callback", "io_callback",
    "jax.debug.callback",
}


def _is_jit_decorator(dec: ast.AST, aliases: dict,
                      wrappers=JIT_WRAPPERS) -> bool:
    """Whether `dec` applies one of `wrappers` — by default everything
    that TRACES its function; XF204 passes the jit family alone (a
    shard_map body is traced but is no compile unit of its own)."""
    name = astutil.canonical(astutil.dotted(dec), aliases)
    if name in wrappers:
        return True
    if isinstance(dec, ast.Call):
        cn = astutil.canonical(astutil.call_name(dec), aliases)
        if cn in wrappers:
            return True
        # functools.partial(jax.jit, ...) as a decorator factory
        if cn in ("functools.partial", "partial") and dec.args:
            return astutil.canonical(
                astutil.dotted(dec.args[0]), aliases) in wrappers
    return False


def _jit_roots(tree: ast.AST, defs: list, aliases: dict) -> tuple:
    """(root qualnames, lambda nodes traced directly)."""
    by_name = astutil.defs_by_name(defs)
    roots: set = set()
    lambdas: list = []
    for qn, node, _cls in defs:
        if any(_is_jit_decorator(d, aliases) for d in node.decorator_list):
            roots.add(qn)
    for caller_qn, node in astutil.scope_sites(tree, defs):
        if not isinstance(node, ast.Call):
            continue
        cn = astutil.canonical(astutil.call_name(node), aliases)
        if cn in HOST_CALLBACK_WRAPPERS:
            continue
        if cn not in JIT_WRAPPERS:
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if isinstance(arg, ast.Name):
                roots.update(astutil.resolve_scoped(arg.id, caller_qn,
                                                    by_name))
            elif isinstance(arg, ast.Lambda):
                lambdas.append(arg)
            elif isinstance(arg, ast.Attribute):
                # self.step / cls.step — match by trailing attribute
                roots.update(astutil.resolve_scoped(arg.attr, caller_qn,
                                                    by_name))
    return roots, lambdas


def _scan_body(body_owner: ast.AST, relpath: str, where: str,
               aliases: dict) -> list:
    out = []
    nodes = astutil.walk_scope(body_owner)
    for sub in nodes:
        if isinstance(sub, ast.Global):
            out.append(Finding(
                rule=RULE, path=relpath, line=sub.lineno,
                message=f"global mutation inside jit-traced code ({where})",
                hint="thread state through the function as an argument "
                     "and return the new value",
            ))
            continue
        if not isinstance(sub, ast.Call):
            continue
        cn = astutil.canonical(astutil.call_name(sub), aliases)
        if cn is None or cn in ALLOWED:
            continue
        why = BANNED_CALLS.get(cn)
        if why is None:
            for pfx, pwhy in BANNED_PREFIXES.items():
                if cn.startswith(pfx):
                    why = pwhy
                    break
        if why is None:
            continue
        out.append(Finding(
            rule=RULE, path=relpath, line=sub.lineno,
            message=f"host-side call `{cn}` inside jit-traced code "
                    f"({where}): {why}",
            hint="hoist the call out of the traced function; for debug "
                 "output use jax.debug.print",
        ))
    return out


@register_pass("jit-purity", (RULE,))
def run(project: Project) -> list:
    findings = []
    for mod in project.modules:
        if mod.tree is None:
            continue
        defs = astutil.func_defs(mod.tree)
        aliases = astutil.import_aliases(mod.tree)
        roots, lambdas = _jit_roots(mod.tree, defs, aliases)
        if not roots and not lambdas:
            continue
        graph = astutil.local_call_graph(defs)
        reach = astutil.reachable(roots, graph)
        by_qn = {qn: node for qn, node, _cls in defs}
        for qn in sorted(reach):
            node = by_qn.get(qn)
            if node is None:
                continue
            where = qn if qn in roots else f"{qn}, reached from a jit root"
            findings.extend(_scan_body(node, mod.relpath, where, aliases))
        for lam in lambdas:
            findings.extend(
                _scan_body(lam, mod.relpath, "lambda traced in place",
                           aliases))
    return findings
