"""XF2xx recompile hazards: patterns that silently thrash the jit cache.

PR 7's CompileRecorder turned "each (program, signature) compiles
exactly once per run" into a runtime `--check` gate; these rules catch
the same class of bug before the code ever runs:

- XF201 jit-in-loop: a `jax.jit(...)` (or immediately-invoked
  `jax.jit(f)(x)`) inside a for/while body builds a FRESH callable —
  and with it a fresh trace + compile — on every iteration. The cache
  keys on the function object; a new object never hits.
- XF202 varying-static-argument: a callable jitted with
  `static_argnums`/`static_argnames` recompiles once per DISTINCT
  value of each static argument. Passing a loop induction variable, or
  different literals across call sites, in a static slot is a
  compile-per-step bug. Loop-variable detection rides the
  flow-sensitive dataflow engine (analysis/dataflow.py): a value is
  flagged only when it still varies with a loop ENCLOSING the call
  site — a loop variable read after its loop (one value per outer
  execution), or a name rebound to a constant inside the loop, no
  longer false-fires, and a value copied OFF the induction variable
  (`n = k; g(1.0, n)`) is now caught. This removed the pass's old
  scope-locality precision caveats.
- XF203 unhashable-static-argument: a list/dict/set literal in a
  static slot raises (static args are cache keys and must hash) — at
  call time, far from the jit site that declared it static.
- XF204 unrecorded-jit: in the engine/serve modules, every jit must
  route through `telemetry.CompileRecorder.wrap` so the exactly-once
  contract stays observable (docs/OBSERVABILITY.md "Compile
  accounting"). A bare `jax.jit` there compiles invisibly — the
  metrics stream cannot prove it didn't recompile.
"""

from __future__ import annotations

import ast
from xflow_tpu.analysis import astutil, dataflow
from xflow_tpu.analysis.core import Finding, Project, register_pass

RULES = ("XF201", "XF202", "XF203", "XF204")

JIT_CALLS = {"jax.jit", "jit", "pjit", "jax.pjit"}

# modules where PR 7's recorder contract applies: every jitted program
# must be wrapped so compile accounting sees it
RECORDER_SCOPED = tuple(dict.fromkeys(astutil.engine_modules().values())) + (
    "xflow_tpu/models/predict.py",
    "xflow_tpu/serve/",
)


def _static_spec(call: ast.Call) -> tuple:
    """(static positions, static names) declared on a jit call."""
    nums: list = []
    names: list = []
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            v = kw.value
            items = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for it in items:
                if isinstance(it, ast.Constant) and isinstance(it.value, int):
                    nums.append(it.value)
        elif kw.arg == "static_argnames":
            v = kw.value
            items = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for it in items:
                s = astutil.const_str(it)
                if s:
                    names.append(s)
    return nums, names


class _StaticSlotHooks(dataflow.Hooks):
    """Dataflow hooks recording the abstract value of every static-slot
    argument at every call site of a statically-jitted name. The
    flow-sensitive loop-variance fact replaces the old name-set
    heuristic (see module docstring: XF202 retrofit)."""

    def __init__(self, jitted_specs: dict):
        self.jitted_specs = jitted_specs  # fname -> (nums, names)
        # (id(call), slot) -> [call node, arg node, joined AbsVal]
        self.sites: dict = {}

    def _record(self, call, slot, arg_node, val) -> None:
        key = (id(call), slot)
        cur = self.sites.get(key)
        if cur is None:
            self.sites[key] = [call, arg_node, val]
        else:
            cur[2] = dataflow.join(cur[2], val)

    def at_call(self, node, callee, argvals, kwvals, env, df, fval):
        fname = astutil.dotted(node.func)
        spec = self.jitted_specs.get(fname)
        if spec is None:
            return None
        nums, names = spec
        for idx in nums:
            if idx < len(node.args):
                self._record(node, idx, node.args[idx], argvals[idx])
        for kw in node.keywords:
            if kw.arg in names and kw.arg in kwvals:
                self._record(node, kw.arg, kw.value, kwvals[kw.arg])
        return None


@register_pass("recompile-hazard", RULES)
def run(project: Project) -> list:
    findings = []
    for mod in project.modules:
        if mod.tree is None:
            continue
        parents = astutil.parent_map(mod.tree)
        aliases = astutil.import_aliases(mod.tree)
        # name -> the jit Call that produced it (for static-arg call sites)
        jitted: dict = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                if astutil.canonical(astutil.call_name(node.value),
                                     aliases) in JIT_CALLS:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            jitted[tgt.id] = node.value

        in_scope = any(mod.relpath.startswith(p) or mod.relpath == p
                       for p in RECORDER_SCOPED)
        wrapped_names: set = set()
        wrapped_factories: set = set()
        if in_scope:
            # names passed to a `.wrap(...)` call anywhere in the module
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Call) and isinstance(
                        node.func, ast.Attribute) and node.func.attr == "wrap":
                    for arg in node.args:
                        nm = astutil.dotted(arg)
                        if nm:
                            wrapped_names.add(nm)
            # factory pattern: `jitted = build(...)` then
            # `recorder.wrap(name, jitted)` — a jit RETURNED from
            # `build` is accounted for at the call site
            for node in ast.walk(mod.tree):
                if (isinstance(node, ast.Assign)
                        and isinstance(node.value, ast.Call)):
                    cn = astutil.call_name(node.value)
                    if cn is None or "." in cn:
                        continue
                    for tgt in node.targets:
                        nm = astutil.dotted(tgt)
                        if nm and nm in wrapped_names:
                            wrapped_factories.add(cn)

        # decorator-form jit in recorder-scoped modules: `@jax.jit` (or
        # `@partial(jax.jit, ...)`) on a def whose name never reaches a
        # `.wrap(...)` call bypasses compile accounting just as surely
        # as the call form below
        if in_scope:
            from xflow_tpu.analysis.passes.jit_purity import _is_jit_decorator

            for node in ast.walk(mod.tree):
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                if not any(_is_jit_decorator(d, aliases, JIT_CALLS)
                           for d in node.decorator_list):
                    continue
                if node.name in wrapped_names:
                    continue
                findings.append(Finding(
                    rule="XF204", path=mod.relpath, line=node.lineno,
                    message="decorator-jitted function not routed through "
                            "CompileRecorder.wrap — compile accounting "
                            "cannot see it (exactly-once contract, "
                            "docs/OBSERVABILITY.md)",
                    hint="drop the decorator and wrap explicitly: "
                         "`recorder.wrap(\"<program>\", jax.jit(fn))`",
                ))

        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            cn = astutil.canonical(astutil.call_name(node), aliases)
            if cn not in JIT_CALLS:
                continue
            # ---- XF201: jit constructed per loop iteration ------------
            if astutil.in_loop(node, parents):
                findings.append(Finding(
                    rule="XF201", path=mod.relpath, line=node.lineno,
                    message=f"`{cn}(...)` inside a loop builds a fresh "
                            "callable — and recompiles — every iteration",
                    hint="hoist the jit out of the loop (the cache keys on "
                         "the function OBJECT; a new object never hits)",
                ))
            # immediately-invoked jit inside any function that also sits
            # in a loop is covered above; bare immediate invocation at
            # module level compiles once and is left alone.
            # ---- XF204: unrecorded jit in recorder-scoped modules -----
            if in_scope:
                parent = parents.get(node)
                ok = False
                # direct: recorder.wrap("name", jax.jit(f))
                enc = astutil.enclosing(node, parents, (ast.Call,))
                if enc is not None and isinstance(enc.func, ast.Attribute) \
                        and enc.func.attr == "wrap":
                    ok = True
                # assigned then wrapped: fn = jax.jit(f); recorder.wrap(fn)
                if isinstance(parent, ast.Assign):
                    for tgt in parent.targets:
                        nm = astutil.dotted(tgt)
                        if nm and nm in wrapped_names:
                            ok = True
                # returned from a factory whose results get wrapped:
                # `def build(): return jax.jit(f)` + `x = build()` +
                # `recorder.wrap(name, x)`
                if not ok and isinstance(parent, ast.Return):
                    fn = astutil.enclosing(
                        node, parents, (ast.FunctionDef,
                                        ast.AsyncFunctionDef))
                    if fn is not None and fn.name in wrapped_factories:
                        ok = True
                if not ok:
                    findings.append(Finding(
                        rule="XF204", path=mod.relpath, line=node.lineno,
                        message="jit program not routed through "
                                "CompileRecorder.wrap — compile accounting "
                                "cannot see it (exactly-once contract, "
                                "docs/OBSERVABILITY.md)",
                        hint="wrap it: `recorder.wrap(\"<program>\", jitted)`"
                             " when a recorder is configured",
                    ))

        # ---- XF203: unhashable literals in static slots (syntactic) ---
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fname = astutil.dotted(node.func)
            if fname not in jitted:
                continue
            nums, names = _static_spec(jitted[fname])
            if not nums and not names:
                continue
            for idx in nums:
                if idx < len(node.args):
                    _check_unhashable(findings, mod, node, fname, idx,
                                      node.args[idx])
            for kw in node.keywords:
                if kw.arg in names:
                    _check_unhashable(findings, mod, node, fname, kw.arg,
                                      kw.value)
        # ---- XF202 (loop variance): flow-sensitive dataflow sweep -----
        specs = {}
        for fname, jcall in jitted.items():
            nums, names = _static_spec(jcall)
            if nums or names:
                specs[fname] = (nums, names)
        if specs:
            hooks = _StaticSlotHooks(specs)
            dataflow.Dataflow(mod, hooks).run_all()
            for (_cid, slot), (call, arg_node, val) in sorted(
                    hooks.sites.items(),
                    key=lambda kv: (kv[1][0].lineno, str(kv[0][1]))):
                if not val.tagged("loopvar"):
                    continue
                # the value must still VARY here: some loop that bound
                # it must enclose this call site (a loop variable read
                # after its loop is one value per outer execution)
                if not _inside_binding_loop(call, val.loops, parents):
                    continue
                fname = astutil.dotted(call.func)
                label = arg_node.id if isinstance(arg_node, ast.Name) \
                    else "<derived from a loop variable>"
                findings.append(Finding(
                    rule="XF202", path=mod.relpath, line=call.lineno,
                    message=f"loop variable `{label}` in static slot "
                            f"{slot!r} of jitted `{fname}` — recompiles "
                            "once per loop value",
                    hint="make the argument dynamic (traced) or hoist "
                         "the loop into the program (lax.scan / "
                         "fori_loop)",
                ))
        # cross-site varying literals in static slots
        _varying_literals(findings, mod, jitted)
    return findings


def _inside_binding_loop(call: ast.AST, loop_ids: frozenset,
                         parents: dict) -> bool:
    cur = parents.get(call)
    while cur is not None:
        if isinstance(cur, (ast.For, ast.AsyncFor, ast.While,
                            ast.ListComp, ast.SetComp, ast.GeneratorExp,
                            ast.DictComp)) and id(cur) in loop_ids:
            return True
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
            return False
        cur = parents.get(cur)
    return False


def _check_unhashable(findings, mod, call, fname, slot, arg):
    if isinstance(arg, (ast.List, ast.Dict, ast.Set)):
        findings.append(Finding(
            rule="XF203", path=mod.relpath, line=call.lineno,
            message=f"unhashable {type(arg).__name__.lower()} literal in "
                    f"static slot {slot!r} of jitted `{fname}` — static "
                    "args are cache keys and must hash",
            hint="pass a tuple (or hoist the structure out of the static "
                 "signature)",
        ))


def _varying_literals(findings, mod, jitted) -> None:
    """Two call sites passing DIFFERENT literals in one static slot ->
    one compile per value (XF202)."""
    if not jitted:
        return
    sites: dict = {}
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        fname = astutil.dotted(node.func)
        if fname not in jitted:
            continue
        nums, names = _static_spec(jitted[fname])
        for idx in nums:
            if idx < len(node.args):
                arg = node.args[idx]
                if isinstance(arg, ast.Constant):
                    sites.setdefault((fname, idx), []).append(
                        (node.lineno, arg.value))
        for kw in node.keywords:
            if kw.arg in names and isinstance(kw.value, ast.Constant):
                sites.setdefault((fname, kw.arg), []).append(
                    (kw.value.lineno, kw.value.value))
    for (fname, slot), vals in sites.items():
        distinct = {repr(v) for _ln, v in vals}
        if len(distinct) > 1:
            line = min(ln for ln, _v in vals)
            findings.append(Finding(
                rule="XF202", path=mod.relpath, line=line,
                message=f"jitted `{fname}` called with "
                        f"{len(distinct)} distinct literals in static slot "
                        f"{slot!r} — one compile per value",
                hint="if the values are genuinely few this may be intended;"
                     " otherwise make the argument dynamic",
            ))
