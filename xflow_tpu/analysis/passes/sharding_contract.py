"""XF7xx sharding contracts: extract and cross-check the engine
builders' partition/donation/scope contracts.

The ROADMAP's unlock item — collapsing the four step builders into one
rule-driven engine — is blocked on exactly what no tool could see:
the builders' sharding contracts (mesh axes, PartitionSpecs, donation,
trace scopes) drift silently. PR 7 had to wire CompileRecorder into
all four separately, and XF204 exists because of that drift. This pass
makes the contracts machine-readable and machine-checked:

- **Extraction** (`extract_contracts`): per engine builder
  (`ENGINE_MODULES`), a normalized record — mesh axes referenced by
  every PartitionSpec and collective, `in_shardings`/`out_shardings`
  and `donate_argnums` per jit program (program names resolved through
  `recorder.wrap`), shard_map in/out specs, per-table-leaf sharding
  declarations, and `jax.named_scope` coverage — emitted as the
  byte-stable `tools/engine_contracts.json` artifact
  (`tools/xflowlint.py --write-contracts` / `--check-contracts`,
  drift = exit 4, distinct from finding growth). The contract matrix
  is the acceptance oracle the future unified builder must reproduce:
  its riskiest step becomes a diff against a checked-in artifact.

- **XF701 undeclared-mesh-axis**: a PartitionSpec referencing an axis
  name not declared by the project mesh (parallel/mesh.py
  DATA_AXIS/TABLE_AXIS) nor by a Mesh(...) constructed in the same
  module. A misspelled axis fails deep inside GSPMD partitioning at
  run time; here it fails in lint.

- **XF702 donated-buffer-read**: flow-sensitive (analysis/dataflow.py)
  — a value whose buffer was handed to a jitted call with
  `donate_argnums` is read again afterwards (including the next
  iteration of a loop that forgot to rebind). Donated buffers are
  invalidated by execution; the read works on CPU test runs and
  corrupts or crashes on TPU.

- **XF703 undonated-state**: a jit of a train step (first parameter
  `state`, the TrainState carrying tables + optimizer state) without
  `donate_argnums` including it. The state is the dominant HBM
  resident; without donation the update holds TWO copies live — the
  PR 7 memory_analysis bug class (docs/PERF.md "HBM residency").

- **XF704 cross-engine-drift**: (a) a builder missing a trace scope
  every other builder covers (the gather/loss/grad/optimizer xprof
  vocabulary, docs/OBSERVABILITY.md) — scope drift is how per-stage
  attribution silently goes blind on one engine; (b) one builder
  declaring two different shardings for the same table leaf across its
  programs (a train step and its sibling eval/opt-state declaration
  disagreeing is exactly the desync XF204's recorder catches only at
  run time).
"""

from __future__ import annotations

import ast
import os
from dataclasses import replace
from typing import Optional

from xflow_tpu.analysis import astutil, dataflow
from xflow_tpu.analysis.core import Finding, Project, register_pass
from xflow_tpu.analysis.passes.recompile import _static_spec

RULES = ("XF701", "XF702", "XF703", "XF704")

# the step builders' modules, each once, in train/engine.py's order
ENGINE_MODULES = tuple(dict.fromkeys(astutil.engine_modules().values()))
SHARED_STEP_MODULE = "xflow_tpu/train/step.py"
# XF704(a)'s vocabulary: the phases every engine's step has
# (telemetry.PHASE_LABELS, docs/OBSERVABILITY.md "Step phases"). A label
# beyond it is not every builder's — "exchange" needs a mesh, "scatter"
# is never written (it is a "gather" under autodiff's `transpose(`), and
# the single-device step's fused "scatter_optimizer" kernel has no
# counterpart on a mesh engine, whose two-pass form reads "scatter" +
# "update". ("gather" is opened where the table lookup is called —
# ops/sorted_table.py, models/ — and so is no builder's to lack.)
STAGE_SCOPES = frozenset({"rows", "update", "health"})
MESH_MODULE = "xflow_tpu/parallel/mesh.py"
ARTIFACT_REL = "tools/engine_contracts.json"

SPEC_CTORS = {"P", "PartitionSpec", "jax.sharding.PartitionSpec"}
NS_CTORS = {"NamedSharding", "jax.sharding.NamedSharding"}
JIT_CALLS = {"jax.jit", "jit", "pjit", "jax.pjit"}
MESH_CTORS = {"Mesh", "jax.sharding.Mesh", "jax.make_mesh"}
COLLECTIVES = {
    "jax.lax.psum", "lax.psum", "jax.lax.pmean", "lax.pmean",
    "jax.lax.psum_scatter", "lax.psum_scatter",
    "jax.lax.all_to_all", "lax.all_to_all",
    "jax.lax.all_gather", "lax.all_gather",
    "jax.lax.axis_index", "lax.axis_index",
}
# delegation calls that inherit the shared single-device step's scopes
SHARED_STEP_BUILDERS = {"make_train_step", "make_eval_step"}
DEFAULT_AXES = ("data", "table")
STATE_PARAM = "state"


# --------------------------------------------------------- axis declarations


def _axis_decls_from_tree(tree) -> tuple:
    """(axis names, {CONST_NAME: value}) declared by one module: string
    constants assigned at module level plus Mesh(...)/make_mesh axis
    tuples."""
    axes: set = set()
    consts: dict = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and isinstance(node.value,
                                                      ast.Constant) \
                and isinstance(node.value.value, str):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    consts[tgt.id] = node.value.value
                    if tgt.id.endswith("_AXIS"):
                        axes.add(node.value.value)
    aliases = astutil.import_aliases(tree)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        cn = astutil.canonical(astutil.call_name(node), aliases)
        if cn not in MESH_CTORS:
            continue
        cands = list(node.args) + [kw.value for kw in node.keywords
                                   if kw.arg == "axis_names"]
        for arg in cands:
            if isinstance(arg, (ast.Tuple, ast.List)):
                names = []
                for el in arg.elts:
                    s = astutil.const_str(el)
                    if s is None and isinstance(el, ast.Name):
                        s = consts.get(el.id)
                    if s is None:
                        names = []
                        break
                    names.append(s)
                axes.update(names)
    return axes, consts


def mesh_decls(project: Project) -> tuple:
    """Project-level declared axes + axis-constant map, anchored at
    parallel/mesh.py (falls back to the canonical ('data', 'table')
    mesh when linting a scratch tree without it)."""
    tree = None
    for mod in project.modules:
        if mod.relpath == MESH_MODULE and mod.tree is not None:
            tree = mod.tree
            break
    if tree is None:
        path = os.path.join(project.root, *MESH_MODULE.split("/"))
        if os.path.exists(path):
            try:
                with open(path, encoding="utf-8", errors="replace") as f:
                    tree = ast.parse(f.read(), filename=path)
            except SyntaxError:
                tree = None
    if tree is None:
        return set(DEFAULT_AXES), {"DATA_AXIS": "data",
                                   "TABLE_AXIS": "table"}
    axes, consts = _axis_decls_from_tree(tree)
    if not axes:
        axes = set(DEFAULT_AXES)
    return axes, consts


# ------------------------------------------------------------------ renderer


class _Renderer:
    """Deterministic, machine-stable rendering of sharding expressions:
    axis constants resolve to their strings, names bound to spec
    constructors resolve through the module-wide alias map, everything
    else renders structurally. No line numbers, no absolute paths —
    the artifact must be byte-stable and the messages baselinable."""

    MAX_DEPTH = 6
    MAX_LEN = 120

    def __init__(self, consts: dict, aliases: dict):
        self.consts = dict(consts)
        self.aliases = aliases
        self.alias_specs: dict = {}

    def seed_alias_specs(self, tree) -> None:
        """name -> rendered spec for every `x = P(...)` / `x =
        NamedSharding(...)` assignment anywhere in the module; a name
        bound to two different specs renders bare (ambiguous)."""
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign) \
                    or not isinstance(node.value, ast.Call):
                continue
            cn = astutil.canonical(astutil.call_name(node.value),
                                   self.aliases)
            if cn not in SPEC_CTORS | NS_CTORS:
                continue
            rendered = self.render(node.value)
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    prev = self.alias_specs.get(tgt.id)
                    if prev is not None and prev != rendered:
                        self.alias_specs[tgt.id] = None  # ambiguous
                    elif prev is None and tgt.id not in self.alias_specs:
                        self.alias_specs[tgt.id] = rendered

    def render(self, node, env: Optional[dict] = None, depth: int = 0) -> str:
        r = self.render_raw(node, env, depth)
        return r if len(r) <= self.MAX_LEN else r[: self.MAX_LEN - 3] + "..."

    def render_raw(self, node, env, depth) -> str:
        if depth > self.MAX_DEPTH:
            return "..."
        if isinstance(node, ast.Constant):
            return repr(node.value)
        if isinstance(node, ast.Name):
            if env is not None:
                v = env.get(node.id)
                if v is not None and v.spec:
                    return v.spec
            alias = self.alias_specs.get(node.id)
            if alias:
                return alias
            if node.id in self.consts:
                return repr(self.consts[node.id])
            return node.id
        if isinstance(node, ast.Attribute):
            return astutil.dotted(node) or (
                self.render_raw(node.value, env, depth + 1) + "." + node.attr)
        if isinstance(node, ast.Tuple):
            inner = ", ".join(self.render_raw(e, env, depth + 1)
                              for e in node.elts)
            return f"({inner},)" if len(node.elts) == 1 else f"({inner})"
        if isinstance(node, ast.List):
            return "[" + ", ".join(self.render_raw(e, env, depth + 1)
                                   for e in node.elts) + "]"
        if isinstance(node, ast.Dict):
            parts = []
            for k, v in zip(node.keys, node.values):
                ks = self.render_raw(k, env, depth + 1) if k is not None \
                    else "**"
                parts.append(f"{ks}: {self.render_raw(v, env, depth + 1)}")
            return "{" + ", ".join(parts) + "}"
        if isinstance(node, ast.DictComp):
            return (f"{{{self.render_raw(node.key, env, depth + 1)}: "
                    f"{self.render_raw(node.value, env, depth + 1)} "
                    "for ...}")
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            return f"[{self.render_raw(node.elt, env, depth + 1)} for ...]"
        if isinstance(node, ast.Starred):
            return "*" + self.render_raw(node.value, env, depth + 1)
        if isinstance(node, ast.Subscript):
            return (self.render_raw(node.value, env, depth + 1) + "["
                    + self.render_raw(node.slice, env, depth + 1) + "]")
        if isinstance(node, ast.Call):
            cn = astutil.canonical(astutil.call_name(node), self.aliases)
            if cn in NS_CTORS:
                # drop the mesh argument: the SPEC is the contract
                spec_arg = node.args[1] if len(node.args) > 1 else (
                    node.args[0] if node.args else None)
                inner = self.render_raw(spec_arg, env, depth + 1) \
                    if spec_arg is not None else ""
                return f"NamedSharding({inner})"
            if cn in SPEC_CTORS:
                parts = [self.render_raw(a, env, depth + 1)
                         for a in node.args]
                return "P(" + ", ".join(parts) + ")"
            label = astutil.call_name(node) or "<call>"
            args = [self.render_raw(a, env, depth + 1) for a in node.args]
            args += [f"{kw.arg}={self.render_raw(kw.value, env, depth + 1)}"
                     for kw in node.keywords if kw.arg]
            return f"{label}({', '.join(args)})"
        if isinstance(node, ast.IfExp):
            return (self.render_raw(node.body, env, depth + 1) + " if ... "
                    "else " + self.render_raw(node.orelse, env, depth + 1))
        try:
            s = ast.unparse(node)
        except Exception:  # pragma: no cover
            s = "<expr>"
        return s


# ------------------------------------------------------- per-module analysis


class _ContractHooks(dataflow.Hooks):
    """Dataflow hooks: jit-record capture + recorder.wrap program
    naming + donated-buffer tracking (XF702)."""

    propagate_returns = True

    def __init__(self, mod, renderer: _Renderer):
        self.mod = mod
        self.renderer = renderer
        self.jits: dict = {}  # id(jit Call) -> record
        self.jit_order: list = []
        self.findings: list = []
        self._flagged: set = set()

    def _program_name(self, node) -> Optional[str]:
        s = astutil.const_str(node)
        if s is not None:
            return s
        if isinstance(node, ast.JoinedStr):
            parts = []
            for v in node.values:
                if isinstance(v, ast.Constant):
                    parts.append(str(v.value))
                elif isinstance(v, ast.FormattedValue):
                    try:
                        parts.append("{" + ast.unparse(v.value) + "}")
                    except Exception:  # pragma: no cover
                        parts.append("{}")
            return "".join(parts)
        return None

    def at_call(self, node, callee, argvals, kwvals, env, df, fval):
        rend = self.renderer
        if callee in JIT_CALLS:
            nums, names = _static_spec(node)
            donate: list = []
            for kw in node.keywords:
                if kw.arg in ("donate_argnums", "donate_argnames"):
                    v = kw.value
                    items = v.elts if isinstance(v, (ast.Tuple, ast.List)) \
                        else [v]
                    for it in items:
                        if isinstance(it, ast.Constant):
                            donate.append(it.value)
            fn_txt = rend.render(node.args[0], env) if node.args else "<fn>"
            rec = {
                "function": fn_txt,
                "fn_ref": argvals[0].ref if argvals else None,
                "donate_argnums": donate,
                "static_argnums": nums,
                "static_argnames": names,
                "in_shardings": None,
                "out_shardings": None,
                "line": node.lineno,
                "name": None,
            }
            for kw in node.keywords:
                if kw.arg in ("in_shardings", "out_shardings"):
                    rec[kw.arg] = rend.render(kw.value, env)
            if id(node) not in self.jits:
                self.jit_order.append(id(node))
            self.jits[id(node)] = rec
            return dataflow.AbsVal(ref=("jit", id(node)), origin=node.lineno)
        if isinstance(node.func, ast.Attribute) and node.func.attr == "wrap" \
                and len(node.args) >= 2:
            nm = self._program_name(node.args[0])
            target = argvals[1]
            if nm is not None and target.ref is not None \
                    and target.ref[0] == "jit":
                rec = self.jits.get(target.ref[1])
                if rec is not None and rec["name"] is None:
                    rec["name"] = nm
            return target  # wrap returns the wrapped callable unchanged
        if callee is not None and callee.split(".")[-1] == "past_cache" \
                and argvals:
            # compile_cache.past_cache(jitted): the same program,
            # compiled past the persistent cache
            return argvals[0]
        if fval.ref is not None and fval.ref[0] == "jit":
            # invoking a locally-jitted program: donate its buffers
            rec = self.jits.get(fval.ref[1])
            for idx in (rec or {}).get("donate_argnums", ()):
                if isinstance(idx, int) and idx < len(node.args):
                    d = astutil.dotted(node.args[idx])
                    if d is not None:
                        cur = env.get(d, dataflow.BOTTOM)
                        env[d] = replace(
                            cur, tags=cur.tags | {"donated"},
                            origin=node.lineno)
            return dataflow.AbsVal(tags=frozenset({"device"}), fresh=True,
                                   origin=node.lineno)
        if callee in SPEC_CTORS | NS_CTORS:
            return dataflow.AbsVal(spec=rend.render(node, env))
        # module-local call: let the engine propagate its return value
        if fval.ref is not None and fval.ref[0] == "def":
            return None
        if callee is not None:
            simple = callee.split(".")[-1]
            if callee in (simple, f"self.{simple}", f"cls.{simple}") \
                    and astutil.resolve_scoped(simple, df.current_qn,
                                               df.by_name):
                return None
        # opaque call: keep textual provenance so `ssh = state_shardings(
        # state, mesh)` renders meaningfully inside a jit contract
        return dataflow.AbsVal(spec=rend.render(node, env))

    def at_load(self, node, name, val, env, df):
        if name is None:
            # un-dotted attribute fallthrough: the base Name load
            # already reported the donated read, with a readable name
            return
        if val.tagged("donated"):
            key = (node.lineno, name)
            if key in self._flagged:
                return
            self._flagged.add(key)
            self.findings.append(Finding(
                rule="XF702", path=self.mod.relpath, line=node.lineno,
                message=(
                    f"`{name}` read after its buffer was donated to a "
                    "jitted call (donate_argnums) — donated buffers are "
                    "invalidated by execution; works on CPU, corrupts "
                    "on TPU"
                ),
                hint="rebind the name to the call's result (state = "
                     "step(state, ...)) or drop the donation",
            ))


def _first_param(fn_node) -> Optional[str]:
    args = fn_node.args
    pos = args.posonlyargs + args.args
    return pos[0].arg if pos else None


def _p_axis_entries(arg, consts: dict):
    """Axis names referenced by one PartitionSpec argument."""
    nodes = arg.elts if isinstance(arg, (ast.Tuple, ast.List)) else [arg]
    for el in nodes:
        s = astutil.const_str(el)
        if s is None and isinstance(el, ast.Name):
            s = consts.get(el.id)
        if s is not None:
            yield s, el


def _flatten_leaf_specs(dict_node, renderer, prefix, out: dict) -> None:
    for k, v in zip(dict_node.keys, dict_node.values):
        key = astutil.const_str(k) if k is not None else None
        if key is None:
            continue
        path = f"{prefix}{key}"
        if isinstance(v, ast.Dict):
            _flatten_leaf_specs(v, renderer, path + ".", out)
            continue
        rendered = renderer.render(v)
        if "P(" in rendered or "NamedSharding(" in rendered:
            out.setdefault(path, set()).add(rendered)


class _ModuleContract:
    """Everything extracted from one module: findings + contract data."""

    def __init__(self, mod, project_axes: set, project_consts: dict):
        self.mod = mod
        tree = mod.tree
        aliases = astutil.import_aliases(tree)
        local_axes, local_consts = _axis_decls_from_tree(tree)
        self.consts = dict(project_consts)
        self.consts.update(local_consts)
        self.declared = set(project_axes) | local_axes
        self.renderer = _Renderer(self.consts, aliases)
        self.renderer.seed_alias_specs(tree)
        self.findings: list = []
        self.axes_referenced: set = set()
        self.scopes: set = set()
        self.scope_lines: list = []
        self.leaf_specs: dict = {}
        self.shard_map_specs: dict = {}
        self.calls_shared_builder = False

        # ---- flow-sensitive sweep: jit records, wrap names, XF702
        hooks = _ContractHooks(mod, self.renderer)
        dataflow.Dataflow(mod, hooks).run_all()
        self.jits = [hooks.jits[i] for i in hooks.jit_order]
        self.findings.extend(hooks.findings)

        # ---- syntactic sweeps
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            cn = astutil.canonical(astutil.call_name(node), aliases)
            if cn in SPEC_CTORS:
                for axis, el in _p_axis_entries_all(node, self.consts):
                    self.axes_referenced.add(axis)
                    if axis not in self.declared:
                        self.findings.append(Finding(
                            rule="XF701", path=mod.relpath,
                            line=el.lineno,
                            message=(
                                f"PartitionSpec references axis {axis!r}, "
                                "not a declared mesh axis "
                                f"({', '.join(sorted(self.declared))}) — "
                                "fails inside GSPMD partitioning at run "
                                "time"
                            ),
                            hint="use the canonical axis constants "
                                 "(parallel/mesh.py DATA_AXIS/TABLE_AXIS)",
                        ))
            elif cn in COLLECTIVES:
                for arg in list(node.args)[1:2] + [
                        kw.value for kw in node.keywords
                        if kw.arg == "axis_name"]:
                    for axis, _el in _p_axis_entries(arg, self.consts):
                        self.axes_referenced.add(axis)
            elif cn is not None and cn.endswith("named_scope") and node.args:
                s = astutil.const_str(node.args[0])
                if s is not None:
                    self.scopes.add(s)
                    self.scope_lines.append(node.lineno)
            elif cn is not None and cn.split(".")[-1] in SHARED_STEP_BUILDERS:
                origin = aliases.get(cn.split(".")[-1], "")
                if origin.startswith("xflow_tpu.train.step."):
                    self.calls_shared_builder = True

        # per-table-leaf shardings from dict literals (incl. TrainState(...));
        # nested dicts flatten through their parent's key path only
        nested: set = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Dict):
                for v in node.values:
                    if isinstance(v, ast.Dict):
                        nested.add(id(v))
        for node in ast.walk(tree):
            if isinstance(node, ast.Dict) and id(node) not in nested:
                _flatten_leaf_specs(node, self.renderer, "", self.leaf_specs)

        # shard_map decorator / call specs
        parents = None
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            cn = astutil.canonical(astutil.call_name(node), aliases)
            if cn is None or cn.split(".")[-1] not in (
                    "shard_map", "smap"):
                continue
            specs = {}
            for kw in node.keywords:
                if kw.arg in ("in_specs", "out_specs"):
                    specs[kw.arg] = self.renderer.render(kw.value)
            if specs:
                if parents is None:  # built once, only when needed
                    parents = astutil.parent_map(tree)
                owner = astutil.enclosing(
                    node, parents,
                    (ast.FunctionDef, ast.AsyncFunctionDef))
                name = owner.name if owner is not None else "<module>"
                self.shard_map_specs.setdefault(name, {}).update(specs)

        # ---- XF703: jit of a train step without state donation
        by_qn = {qn: n for qn, n, _c in astutil.func_defs(tree)}
        for rec in self.jits:
            ref = rec.get("fn_ref")
            if ref is None or ref[0] != "def":
                continue
            fn_node = by_qn.get(ref[1])
            if fn_node is None or _first_param(fn_node) != STATE_PARAM:
                continue
            if 0 not in rec["donate_argnums"] \
                    and STATE_PARAM not in rec["donate_argnums"]:
                self.findings.append(self._xf703(rec["line"]))
        # decorator form
        for qn, fn_node, _cls in astutil.func_defs(tree):
            if _first_param(fn_node) != STATE_PARAM:
                continue
            for dec in fn_node.decorator_list:
                # the jit family ONLY (shard_map/grad/vmap wrappers have
                # no donation contract): @jax.jit, @jax.jit(...), or
                # @partial(jax.jit, ...)
                name = astutil.canonical(astutil.dotted(dec), aliases)
                is_jit = name in JIT_CALLS
                if not is_jit and isinstance(dec, ast.Call):
                    cn = astutil.canonical(astutil.call_name(dec), aliases)
                    if cn in JIT_CALLS:
                        is_jit = True
                    elif cn in ("functools.partial", "partial") and dec.args:
                        is_jit = astutil.canonical(
                            astutil.dotted(dec.args[0]), aliases) in JIT_CALLS
                if not is_jit:
                    continue
                donated = isinstance(dec, ast.Call) and any(
                    kw.arg in ("donate_argnums", "donate_argnames")
                    for kw in dec.keywords)
                if not donated:
                    self.findings.append(self._xf703(fn_node.lineno))
                break

    def _xf703(self, line: int) -> Finding:
        return Finding(
            rule="XF703", path=self.mod.relpath, line=line,
            message=(
                "train-step jit takes the TrainState (tables + optimizer "
                "state) without donate_argnums — the update keeps TWO "
                "copies of the dominant HBM resident live (double-HBM "
                "residency, docs/PERF.md)"
            ),
            hint="donate the state: jax.jit(step, donate_argnums=(0,))",
        )

    def contract(self) -> dict:
        programs: dict = {}
        unnamed = 0
        for rec in self.jits:
            name = rec["name"]
            if name is None:
                unnamed += 1
                name = f"unnamed:{rec['function']}:{unnamed}"
            if name in programs:
                # two jits wrapped under one recorder name must BOTH
                # stay visible to the drift gate — never shadow one
                n = 2
                while f"{name}#{n}" in programs:
                    n += 1
                name = f"{name}#{n}"
            programs[name] = {
                "function": rec["function"],
                "donate_argnums": sorted(
                    x for x in rec["donate_argnums"]
                    if isinstance(x, int)),
                "static_argnums": sorted(rec["static_argnums"]),
                "static_argnames": sorted(rec["static_argnames"]),
                "in_shardings": rec["in_shardings"],
                "out_shardings": rec["out_shardings"],
            }
        return {
            "axes_referenced": sorted(self.axes_referenced),
            "scopes": sorted(self.scopes),
            "programs": programs,
            "leaf_specs": {k: sorted(v)
                           for k, v in sorted(self.leaf_specs.items())},
            "shard_map_specs": {k: dict(sorted(v.items()))
                                for k, v in
                                sorted(self.shard_map_specs.items())},
        }


def _p_axis_entries_all(call: ast.Call, consts: dict):
    for arg in call.args:
        yield from _p_axis_entries(arg, consts)


# --------------------------------------------------------------- entry points


def _analyze(project: Project) -> tuple:
    """-> (findings, {relpath: _ModuleContract for engine modules})."""
    axes, consts = mesh_decls(project)
    findings: list = []
    engines: dict = {}
    for mod in project.modules:
        if mod.tree is None:
            continue
        # cheap pre-filter: modules with no sharding/jit surface skip the
        # flow-sensitive sweep entirely
        if not any(tok in mod.source for tok in (
                "PartitionSpec", "NamedSharding", "jax.jit", "pjit",
                "named_scope", "shard_map", "donate_argnums")):
            continue
        mc = _ModuleContract(mod, axes, consts)
        findings.extend(mc.findings)
        if mod.relpath in ENGINE_MODULES:
            engines[mod.relpath] = mc

    # ---- XF704(a): scope drift across engine builders. The comparison
    # ROSTER is always the full builder set — builders a partial scan
    # (--changed, a subtree) left out load from disk for comparison
    # only, so a partial scan's verdicts match the full tree's (findings
    # fire solely on SCANNED modules; like mesh_decls' axes anchor)
    roster: dict = dict(engines)
    from xflow_tpu.analysis.core import Module, _read

    for rel in ENGINE_MODULES:
        if not engines:
            break  # no scanned builder -> nothing XF704 could fire on
        if rel in roster:
            continue
        path = os.path.join(project.root, *rel.split("/"))
        if not os.path.exists(path):
            continue
        m = Module(path, rel, _read(path))
        if m.tree is not None:
            roster[rel] = _ModuleContract(m, axes, consts)
    if len(roster) >= 2:
        shared = roster.get(SHARED_STEP_MODULE)
        effective: dict = {}
        for rel, mc in roster.items():
            if rel != SHARED_STEP_MODULE and mc.calls_shared_builder \
                    and shared is None:
                # delegating builder whose delegate is unreadable: its
                # effective scope set is unknowable — never guess a drift
                effective[rel] = None
                continue
            eff = set(mc.scopes)
            if rel != SHARED_STEP_MODULE and mc.calls_shared_builder:
                eff |= shared.scopes
            effective[rel] = eff
        for rel, mc in sorted(roster.items()):
            if rel not in engines or effective[rel] is None:
                continue  # unscanned roster members are comparison-only
            others = [effective[r] for r in roster
                      if r != rel and effective[r] is not None]
            if not others:
                continue
            everywhere_else = set.intersection(*others) & STAGE_SCOPES
            for scope in sorted(everywhere_else - effective[rel]):
                line = min(mc.scope_lines) if mc.scope_lines else 1
                findings.append(Finding(
                    rule="XF704", path=rel, line=line,
                    message=(
                        f"engine builder is missing trace scope "
                        f"{scope!r} that every other engine builder "
                        "covers — per-stage xprof attribution goes "
                        "blind on this engine (contract matrix, "
                        "tools/engine_contracts.json)"
                    ),
                    hint=f"add `with jax.named_scope({scope!r}):` around "
                         "the corresponding stage, or regenerate the "
                         "contract matrix if the vocabulary changed",
                ))
    # ---- XF704(b): intra-builder table-leaf spec disagreement
    for rel, mc in sorted(engines.items()):
        for path, specs in sorted(mc.leaf_specs.items()):
            if len(specs) > 1:
                findings.append(Finding(
                    rule="XF704", path=rel, line=1,
                    message=(
                        f"table leaf {path!r} is declared with "
                        f"{len(specs)} different shardings within one "
                        f"builder: {sorted(specs)} — its programs will "
                        "disagree about where the table lives"
                    ),
                    hint="hoist the sharding into one shared declaration",
                ))
    return findings, engines


def extract_contracts(project: Project) -> dict:
    """The engine-contract matrix (tools/engine_contracts.json): the
    machine-readable acceptance oracle for the ROADMAP's unified-builder
    refactor. Deterministic function of the sources — byte-stable."""
    _findings, engines = _analyze(project)
    axes, _consts = mesh_decls(project)
    return {
        "_comment": (
            "Engine sharding-contract matrix, extracted by xflowlint's "
            "XF7xx pass (analysis/passes/sharding_contract.py). "
            "Regenerate with `python tools/xflowlint.py "
            "--write-contracts`; CI fails with exit 4 on drift "
            "(tools/smoke_lint.sh). The future unified step builder "
            "must reproduce this matrix (ROADMAP: one engine, "
            "rule-driven sharding)."
        ),
        "declared_mesh_axes": sorted(axes),
        "engines": {rel: mc.contract()
                    for rel, mc in sorted(engines.items())},
    }


def render_artifact(contracts: dict) -> str:
    import json

    return json.dumps(contracts, indent=2, sort_keys=True) + "\n"


@register_pass("sharding-contract", RULES, scope="project")
def run(project: Project) -> list:
    findings, _engines = _analyze(project)
    return findings
