"""The IR tier: jaxpr-grounded semantic extraction for xflowlint.

The AST tier (core.py + passes/) deliberately never imports the code
under analysis. This module is the OTHER tier, with the opposite
contract stated just as strictly: it imports the engine modules under
``JAX_PLATFORMS=cpu`` and lowers each step builder's jitted programs to
jaxprs on abstract ``jax.ShapeDtypeStruct`` inputs derived from the
config schema — **no execution, no TPU, trace-only** (tracing and
``.lower()`` build the IR; nothing is compiled for or dispatched to a
device, and ``cost_analysis`` runs client-side on the lowered-but-not-
compiled module).

It is designed to run in a SUBPROCESS (``python -m
xflow_tpu.analysis.ir --root R``) so that

- the jax environment is pinned (CPU platform, a forced 8-device host
  platform so the ('data','table') = (4,2) mesh programs lower the
  same way on every machine — the worklist artifact must be
  byte-stable),
- a scratch tree under ``--root`` is imported INSTEAD of the installed
  package (PYTHONPATH isolation), and
- an unimportable tree or a jax-less machine degrades to a clean
  "unavailable" verdict (exit 5) the AST tier can report and continue
  past — scratch-copy AST-only linting keeps working.

What it extracts, per program in ``PROGRAMS`` (the engine
builders' train/eval/predict programs across the model variants the
ROADMAP's kernel arc targets):

- op histogram, gather/scatter counts, dtype census, and flop/byte
  estimates (``lowered.cost_analysis()``) — the **contracts v2**
  section of ``tools/engine_contracts.json``;
- gather → elementwise-chain → scatter-add subgraphs over table-sized
  operands, with shapes/dtypes/byte estimates and source anchors —
  the **fusion worklist** (``tools/fusion_worklist.json``), i.e. the
  Pallas kernel arc's machine-checked target list (XF801);
- widening ``convert_element_type`` ops over large operands (XF802);
- stacked ``scan`` outputs no consumer reads (XF803);
- the lowered signature facts (donation per argument, sharding
  annotations present) the XF804 AST/IR cross-check compares against
  the AST tier's extracted contracts.

The jitted programs are captured through the builders' own
``recorder`` seam (telemetry.CompileRecorder): a capturing recorder
whose ``wrap(name, fn)`` raises, so the lazily-jitting builders
(GSPMD, fullshard) surrender their jit object at the wrap site without
the call ever executing.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

from xflow_tpu.analysis.astutil import engine_modules

# primitives whose operand-0 is a table being read / written sparsely
GATHER_PRIMS = ("gather",)
SCATTER_PRIMS = ("scatter", "scatter-add", "scatter-mul", "scatter-max",
                 "scatter-min")
# a chain participant must touch at least this many elements to count
# as "the table" (filters per-row/per-batch scatters out of XF801)
MIN_TABLE_ELEMS = 1 << 16
# XF802 only cares about big operands (a scalar upcast is free)
MIN_CONVERT_ELEMS = 1 << 16
WIDENING = {("bfloat16", "float32"), ("float16", "float32"),
            ("bfloat16", "float64"), ("float16", "float64")}

# elementwise / selection primitives: a chain's "update math" between
# the gather and the scatter (FTRL/SGD are exactly these)
ELEMENTWISE_PRIMS = frozenset({
    "add", "add_any", "sub", "mul", "div", "neg", "abs", "sign", "sqrt",
    "rsqrt", "exp", "log", "log1p", "logistic", "tanh", "pow",
    "integer_pow", "max", "min", "select_n", "and", "or", "xor", "not",
    "eq", "ne", "lt", "le", "gt", "ge", "is_finite",
    "convert_element_type", "copy", "square",
})

# the program matrix: every entry lowers one recorder-named jit program
# of one engine builder under one config variant. Keys are
# "<recorder name>[<variant>]" — recorder names repeat across configs
# ("train_step" serves both the LR and FM variants), the bracket makes
# them unique and greppable.
# The engine column names an engine of train/engine.py, which says
# where its builder lives.
_PROGRAMS = (
    # key, engine, builder, config overrides, batch
    ("train_step[lr]", "row_major", "single_train",
     {"model.name": "lr"}, "rowmajor"),
    ("predict[lr]", "row_major", "single_eval",
     {"model.name": "lr"}, "rowmajor"),
    ("train_step[fm]", "row_major", "single_train",
     {"model.name": "fm"}, "rowmajor"),
    # the kernel arc's marquee target: the sorted fused path (on CPU the
    # scatter+FTRL fusion falls back to gather/scatter + elementwise XLA
    # ops — exactly the chain the Pallas kernel replaces)
    ("train_step[fm.sorted]", "sorted", "single_train",
     {"model.name": "fm"}, "sorted_flat"),
    ("train_step.gspmd[lr]", "gspmd", "gspmd_train",
     {"model.name": "lr"}, "rowmajor"),
    ("predict.gspmd[lr]", "gspmd", "gspmd_eval",
     {"model.name": "lr"}, "rowmajor"),
    # the fullshard step is two programs, cut where the state is
    # written: the gradient (exchange, gather, scatter) and the update
    # (guard + optimizer sweep)
    ("train_step.fullshard.fm[fm]", "fullshard", "fullshard_train",
     {"model.name": "fm"}, "fullshard"),
    ("update_step.fullshard.fm[fm]", "fullshard", "fullshard_update",
     {"model.name": "fm"}, "fullshard"),
    ("predict.fullshard.fm[fm]", "fullshard", "fullshard_eval",
     {"model.name": "fm"}, "fullshard"),
)
# key, engine module (repo-relative), builder, config overrides, batch
_MODULE_OF = engine_modules()
PROGRAMS = tuple(
    (key, _MODULE_OF[engine], *rest) for key, engine, *rest in _PROGRAMS
)

# mesh shape every sharded program lowers against (forced host devices)
MESH_DATA, MESH_TABLE = 4, 2
FORCED_DEVICES = MESH_DATA * MESH_TABLE

EXIT_UNAVAILABLE = 5


class _Captured(Exception):
    """Raised by the capturing recorder at the wrap site: carries the
    jit object out of a lazily-jitting builder without executing it."""

    def __init__(self, name, fn):
        super().__init__(name)
        self.name, self.fn = name, fn


class _CapturingRecorder:
    """`stop_at`: the prefix of the program's recorder name to stop at
    (None: the first wrap); earlier wraps are kept in `seen`."""

    def __init__(self, stop_at=None):
        self.stop_at, self.seen = stop_at, {}

    def wrap(self, name, fn, **static_fields):
        if self.stop_at is None or name.startswith(self.stop_at):
            raise _Captured(name, fn)
        self.seen[name] = fn
        return fn


def _capture(thunk):
    """Run a builder (or its call seam) until recorder.wrap fires."""
    try:
        thunk()
    except _Captured as c:
        return c.name, c.fn
    raise RuntimeError("builder returned without reaching recorder.wrap")


# ------------------------------------------------------ abstract inputs


def _abstract_state(model, opt, cfg):
    """ShapeDtypeStruct TrainState via eval_shape — the real init
    traced abstractly, nothing allocated."""
    import jax

    from xflow_tpu.train.state import init_state

    return jax.eval_shape(lambda: init_state(model, opt, cfg))


def _with_shardings(tree, shardings):
    import jax

    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings)


def _rowmajor_batch(cfg, mesh=None):
    import jax
    import jax.numpy as jnp

    sds = jax.ShapeDtypeStruct
    B, F = cfg.data.batch_size, cfg.data.max_nnz
    sh = {}
    if mesh is not None:
        from xflow_tpu.parallel.mesh import batch_sharding

        sh = batch_sharding(mesh)
    mk = lambda k, shape, dt: sds(shape, dt, sharding=sh.get(k))
    return {
        "slots": mk("slots", (B, F), jnp.int32),
        "fields": mk("fields", (B, F), jnp.int32),
        "mask": mk("mask", (B, F), jnp.float32),
        "labels": mk("labels", (B,), jnp.float32),
        "row_mask": mk("row_mask", (B,), jnp.float32),
    }


def _sorted_flat_batch(cfg):
    """Single-device flat sorted plan (ops/sorted_table plan shapes)."""
    import jax
    import jax.numpy as jnp

    from xflow_tpu.ops.sorted_table import CHUNK, sorted_window

    sds = jax.ShapeDtypeStruct
    B, F = cfg.data.batch_size, cfg.data.max_nnz
    npad = (B * F // CHUNK + 2) * CHUNK
    n_win = cfg.num_slots // sorted_window(cfg)
    return {
        "sorted_slots": sds((npad,), jnp.int32),
        "sorted_row": sds((npad,), jnp.int32),
        "sorted_mask": sds((npad,), jnp.float32),
        "win_off": sds((n_win + 1,), jnp.int32),
        "labels": sds((B,), jnp.float32),
        "row_mask": sds((B,), jnp.float32),
    }


def _fullshard_batch(cfg, mesh):
    import jax
    import jax.numpy as jnp

    from xflow_tpu.ops.sorted_table import sorted_window
    from xflow_tpu.parallel.mesh import (
        DATA_AXIS, TABLE_AXIS, batch_sharding,
    )
    from xflow_tpu.parallel.sorted_fullshard import fullshard_capacity

    sds = jax.ShapeDtypeStruct
    sh = batch_sharding(mesh)
    B = cfg.data.batch_size
    D, T = mesh.shape[DATA_AXIS], mesh.shape[TABLE_AXIS]
    cap = fullshard_capacity(cfg, mesh)
    wpo = (cfg.num_slots // sorted_window(cfg)) // (D * T)
    mk = lambda k, shape, dt: sds(shape, dt, sharding=sh[k])
    return {
        "fs_slots": mk("fs_slots", (D, T, D, cap), jnp.int32),
        "fs_row": mk("fs_row", (D, T, D, cap), jnp.int32),
        "fs_mask": mk("fs_mask", (D, T, D, cap), jnp.float32),
        "fs_off": mk("fs_off", (D, T, D, wpo + 1), jnp.int32),
        "labels": mk("labels", (B,), jnp.float32),
        "row_mask": mk("row_mask", (B,), jnp.float32),
    }


# -------------------------------------------------------- program build


def _build_program(key, engine, builder, overrides, batch_kind):
    """-> (recorder name, jit object, (arg pytrees...), cfg)."""
    from xflow_tpu.config import Config, override
    from xflow_tpu.models import get_model
    from xflow_tpu.optim import get_optimizer

    needs_mesh = builder not in ("single_train", "single_eval")
    ov = dict(overrides)
    if needs_mesh:
        ov.update({"mesh.data": MESH_DATA, "mesh.table": MESH_TABLE})
    cfg = override(Config(), **ov)
    model = get_model(cfg.model.name)
    opt = get_optimizer(cfg.optim.name)
    state = _abstract_state(model, opt, cfg)
    cap = _CapturingRecorder(
        "update_step" if builder == "fullshard_update" else None)

    if builder == "single_train":
        from xflow_tpu.train.step import make_train_step

        name, fn = _capture(lambda: make_train_step(
            model, opt, cfg, jit=True, recorder=cap))
        batch = _rowmajor_batch(cfg) if batch_kind == "rowmajor" \
            else _sorted_flat_batch(cfg)
        return name, fn, (state, batch), cfg
    if builder == "single_eval":
        from xflow_tpu.train.step import make_eval_step

        name, fn = _capture(lambda: make_eval_step(
            model, cfg, jit=True, recorder=cap))
        return name, fn, (state.tables, _rowmajor_batch(cfg)), cfg

    from xflow_tpu.parallel.mesh import make_mesh, state_shardings

    mesh = make_mesh(cfg)
    if builder == "gspmd_train":
        from xflow_tpu.parallel.train_step import make_sharded_train_step

        st = _with_shardings(state, state_shardings(state, mesh))
        batch = _rowmajor_batch(cfg, mesh)
        call = make_sharded_train_step(model, opt, cfg, mesh, recorder=cap)
        name, fn = _capture(lambda: call(st, batch))
        return name, fn, (st, batch), cfg
    if builder == "gspmd_eval":
        from xflow_tpu.parallel.train_step import make_sharded_eval_step

        st = _with_shardings(state, state_shardings(state, mesh))
        batch = _rowmajor_batch(cfg, mesh)
        call = make_sharded_eval_step(model, cfg, mesh, recorder=cap)
        name, fn = _capture(lambda: call(st.tables, batch))
        return name, fn, (st.tables, batch), cfg
    if builder in ("fullshard_train", "fullshard_update"):
        import jax

        from xflow_tpu.parallel.sorted_fullshard import (
            make_fullshard_train_step,
        )

        st = _with_shardings(state, state_shardings(state, mesh))
        batch = _fullshard_batch(cfg, mesh)
        call = make_fullshard_train_step(opt, cfg, mesh, recorder=cap)
        name, fn = _capture(lambda: call(st, batch))
        keys = ("fs_slots", "fs_row", "fs_mask", "fs_off", "labels",
                "row_mask")
        (table,) = st.tables.values()
        grad_args = (table, {k: batch[k] for k in keys})
        if builder == "fullshard_train":
            return name, fn, grad_args, cfg
        # the update takes what the gradient program hands back
        (grad_fn,) = cap.seen.values()
        (loss, rows), grads = jax.eval_shape(grad_fn, *grad_args)
        return name, fn, (st, grads, loss, rows), cfg
    if builder == "fullshard_eval":
        from xflow_tpu.parallel.sorted_fullshard import (
            make_fullshard_eval_step,
        )

        st = _with_shardings(state, state_shardings(state, mesh))
        batch = _fullshard_batch(cfg, mesh)
        call = make_fullshard_eval_step(cfg, mesh, recorder=cap)
        name, fn = _capture(lambda: call(st.tables, batch))
        keys = ("fs_slots", "fs_row", "fs_mask", "fs_off", "labels")
        return name, fn, (st.tables, {k: batch[k] for k in keys}), cfg
    raise ValueError(f"unknown builder kind {builder!r}")


# -------------------------------------------------------- jaxpr analysis


def _iter_eqns(jaxpr):
    """Every eqn in a jaxpr, recursing into sub-jaxpr params (pjit,
    scan, shard_map, custom_jvp, ...). Params hold either ClosedJaxprs
    (with a .jaxpr) or plain Jaxprs (with .eqns directly) — shard_map
    passes the latter."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else [v]
            for x in vs:
                if hasattr(x, "eqns"):
                    yield from _iter_eqns(x)
                elif hasattr(getattr(x, "jaxpr", None), "eqns"):
                    yield from _iter_eqns(x.jaxpr)


def _src_frames(eqn, root):
    """Repo-relative (file, line) frames of an eqn's traceback,
    innermost first, excluding the analysis tier itself."""
    out = []
    tb = getattr(eqn.source_info, "traceback", None)
    if tb is None:
        return out
    prefix = os.path.abspath(root) + os.sep
    for fr in tb.frames:
        fn = fr.file_name
        if not fn.startswith(prefix):
            continue
        rel = fn[len(prefix):].replace(os.sep, "/")
        if rel.startswith("xflow_tpu/analysis/") or rel.startswith("tools/"):
            continue
        out.append((rel, fr.line_num))
    return out


def _anchor(frames, engine):
    """Innermost frame inside the program's engine module, else the
    innermost repo frame — the file:line a finding points at."""
    for rel, line in frames:
        if rel == engine:
            return [rel, line]
    return list(frames[0]) if frames else [engine, 1]


def _aval(var):
    return getattr(var, "aval", None)


def _nelems(aval):
    n = 1
    for d in aval.shape:
        n *= int(d)
    return n


def analyze_jaxpr(jaxpr, root, engine, table_names):
    """Semantic facts of one traced program's jaxpr.

    `table_names`: {shape tuple -> leaf name} from the abstract state,
    to label chains with the table they stream."""
    histogram: dict = {}
    dtype_census: dict = {}
    gathers: list = []
    scatters: list = []
    converts: list = []
    scans: list = []
    table_sweeps: dict = {}  # shape -> elementwise-eqn count at shape
    for eqn in _iter_eqns(jaxpr):
        name = eqn.primitive.name
        histogram[name] = histogram.get(name, 0) + 1
        for v in eqn.outvars:
            av = _aval(v)
            if av is not None and hasattr(av, "dtype"):
                dt = str(av.dtype)
                dtype_census[dt] = dtype_census.get(dt, 0) + 1
        if name in GATHER_PRIMS or name in SCATTER_PRIMS:
            op_av = _aval(eqn.invars[0]) if eqn.invars else None
            if op_av is None or _nelems(op_av) < MIN_TABLE_ELEMS:
                continue
            idx_av = _aval(eqn.invars[1]) if len(eqn.invars) > 1 else None
            # gather/scatter indices are [..., index_depth]: the
            # occurrence count is every dim but the trailing one
            occ = 0
            if idx_av is not None and idx_av.shape:
                occ = _nelems(idx_av) // max(int(idx_av.shape[-1]), 1)
            rec = {
                "shape": [int(d) for d in op_av.shape],
                "dtype": str(op_av.dtype),
                "occ": occ,
                "src": _anchor(_src_frames(eqn, root), engine),
            }
            (gathers if name in GATHER_PRIMS else scatters).append(rec)
        elif name == "convert_element_type":
            in_av = _aval(eqn.invars[0]) if eqn.invars else None
            out_av = _aval(eqn.outvars[0]) if eqn.outvars else None
            if in_av is None or out_av is None:
                continue
            pair = (str(getattr(in_av, "dtype", "")),
                    str(getattr(out_av, "dtype", "")))
            if pair in WIDENING and _nelems(in_av) >= MIN_CONVERT_ELEMS:
                converts.append({
                    "from": pair[0], "to": pair[1],
                    "shape": [int(d) for d in in_av.shape],
                    "elems": _nelems(in_av),
                    "src": _anchor(_src_frames(eqn, root), engine),
                })
        elif name == "scan":
            scans.append(_analyze_scan(eqn, root, engine))
        if name in ELEMENTWISE_PRIMS:
            for v in eqn.outvars:
                av = _aval(v)
                if av is not None and _nelems(av) >= MIN_TABLE_ELEMS:
                    shp = tuple(int(d) for d in av.shape)
                    table_sweeps[shp] = table_sweeps.get(shp, 0) + 1
    chains = _chains(gathers, scatters, table_sweeps, table_names)
    scans = [s for s in scans if s["dead_outputs"]]
    return {
        "op_histogram": dict(sorted(histogram.items())),
        "dtype_census": dict(sorted(dtype_census.items())),
        "gathers": len(gathers),
        "scatters": len(scatters),
        "chains": chains,
        "converts": converts,
        "scans": scans,
    }


def _analyze_scan(eqn, root, engine):
    """Dead stacked outputs (DropVar pasts the carry). A carry leaf the
    body returns unchanged is not looked for: `lax.scan` forwards such
    a leaf out of the loop itself while tracing (it reaches the jaxpr
    as a constant of the scan, not as a carry)."""
    num_carry = int(eqn.params.get("num_carry", 0))
    dead = []
    for i, v in enumerate(eqn.outvars[num_carry:]):
        if type(v).__name__ == "DropVar":
            dead.append(i)
    return {
        "dead_outputs": dead,
        "length": int(eqn.params.get("length", 0) or 0),
        "src": _anchor(_src_frames(eqn, root), engine),
    }


def _chains(gathers, scatters, table_sweeps, table_names):
    """Group gather/scatter records into per-(shape, dtype) chains —
    the gather → elementwise → scatter-add subgraphs the fusion
    worklist records. A chain needs at least one scatter (a forward-
    only gather is not an update path)."""
    by_key: dict = {}
    for kind, recs in (("gather", gathers), ("scatter", scatters)):
        for r in recs:
            key = (tuple(r["shape"]), r["dtype"])
            ent = by_key.setdefault(key, {"gather": [], "scatter": []})
            ent[kind].append(r)
    chains = []
    for (shape, dtype), ent in sorted(by_key.items()):
        if not ent["scatter"]:
            continue
        table = table_names.get(tuple(shape))
        sweep_shape = tuple(shape)
        if table is None:
            # shard_map bodies see PER-SHARD table shapes: match a state
            # leaf with the same trailing dims whose slot dim this shape
            # divides (the worklist entry reports the shard shape — the
            # per-device kernel target). The optimizer sweep runs on
            # the FULL table outside the shard_map body, so the chain's
            # elementwise ops are counted at the matched full shape.
            for full_shape, name in sorted(table_names.items()):
                if (len(full_shape) == len(shape)
                        and full_shape[1:] == tuple(shape[1:])
                        and shape[0] and full_shape[0] % shape[0] == 0):
                    table = f"{name}/shard"
                    sweep_shape = full_shape
                    break
        itemsize = 2 if dtype in ("bfloat16", "float16") else 4
        nbytes = lambda shp: itemsize * int(math.prod(shp))
        table_bytes = nbytes(shape)
        occ = max([r["occ"] for r in ent["gather"] + ent["scatter"]] or [0])
        row_bytes = table_bytes // shape[0] if shape else itemsize
        sweeps = table_sweeps.get(tuple(shape), 0) \
            or table_sweeps.get(sweep_shape, 0)
        n_g, n_s = len(ent["gather"]), len(ent["scatter"])
        chains.append({
            "table": table or "?",
            "table_shape": list(shape),
            "table_dtype": dtype,
            "table_bytes": table_bytes,
            "occurrences": occ,
            "gathers": n_g,
            "scatters": n_s,
            "elementwise_table_ops": sweeps,
            # rough HBM traffic of the unfused chain: each gather/
            # scatter moves ~occ stored rows, each table-wide
            # elementwise op re-streams the (full) table once
            "est_bytes_per_step": (n_g + n_s) * occ * row_bytes
            + sweeps * nbytes(sweep_shape),
            "gather_at": ent["gather"][0]["src"] if ent["gather"] else None,
            "scatter_at": ent["scatter"][0]["src"],
        })
    return chains


# ------------------------------------------------------------ extraction


def extract_program(key, engine, builder, overrides, batch_kind, root):
    name, fn, args, cfg = _build_program(key, engine, builder, overrides,
                                         batch_kind)
    traced = fn.trace(*args)
    facts = analyze_jaxpr(traced.jaxpr.jaxpr, root, engine,
                          _table_names(cfg))
    lowered = traced.lower()
    cost = None
    try:
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if ca:
            cost = {
                "flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            }
    except Exception:
        cost = None
    donated = sorted(
        i for i, arg in enumerate(args)
        if _all_donated(traced, i, len(args)))
    mlir_text = lowered.as_text()
    has_shardings = "mhlo.sharding" in mlir_text \
        or "sdy.sharding" in mlir_text
    facts.update({
        "engine": engine,
        "recorder_name": name,
        "config": dict(sorted(overrides.items())),
        "batch": batch_kind,
        "donated_args": donated,
        "has_sharding_annotations": bool(has_shardings),
        "cost": cost,
    })
    return facts


def _table_names(cfg):
    """{leaf shape -> table name} for chain labeling, from the config's
    own state: a program may take the whole state, its tables, or one
    table alone."""
    from xflow_tpu.models import get_model
    from xflow_tpu.optim import get_optimizer

    tables = _abstract_state(get_model(cfg.model.name),
                             get_optimizer(cfg.optim.name), cfg).tables
    return {tuple(int(d) for d in leaf.shape): name
            for name, leaf in sorted(tables.items())}


def _all_donated(traced, idx, n_args):
    """Whether every leaf of top-level positional arg `idx` is donated
    in the lowered signature (args_info is the ground truth — the
    Traced.donate_argnums attribute does not report user argnums)."""
    import jax

    infos = traced.args_info
    if isinstance(infos, tuple) and len(infos) == 2 \
            and isinstance(infos[1], dict):
        infos = infos[0]  # ((args...), kwargs) → positional args
    leaves = jax.tree.leaves(infos[idx]) if idx < len(infos) else []
    return bool(leaves) and all(getattr(a, "donated", False)
                                for a in leaves)


def extract_all(root):
    """Lower and analyze every program in PROGRAMS. Returns the facts
    dict (deterministic given a fixed jax version and device count)."""
    import jax

    programs: dict = {}
    errors: list = []
    for key, engine, builder, overrides, batch_kind in PROGRAMS:
        try:
            programs[key] = extract_program(key, engine, builder,
                                            overrides, batch_kind, root)
        except Exception as e:  # one broken builder must not hide the rest
            errors.append({"program": key, "error": f"{type(e).__name__}: {e}"})
    return {
        "ok": True,
        "jax_version": jax.__version__,
        "device_count": len(jax.devices()),
        "mesh": [MESH_DATA, MESH_TABLE],
        "programs": programs,
        "errors": errors,
    }


# ------------------------------------------------------------------ CLI


def _pin_env():
    """Pin the jax environment BEFORE jax import: CPU platform, forced
    8-device host platform (deterministic mesh programs everywhere)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={FORCED_DEVICES}")
    os.environ["XLA_FLAGS"] = " ".join(flags)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="xflow-ir", description=__doc__)
    ap.add_argument("--root", default=os.getcwd(),
                    help="tree whose engine modules to import and lower")
    ap.add_argument("--probe", action="store_true",
                    help="only report availability (jax importable, "
                         "tree importable)")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    _pin_env()
    # the --root tree, not any installed copy, must win the import
    sys.path.insert(0, root)
    for m in [m for m in sys.modules
              if m == "xflow_tpu" or m.startswith("xflow_tpu.")]:
        if m.startswith("xflow_tpu.analysis") or m == "xflow_tpu":
            continue
        del sys.modules[m]
    try:
        import jax
    except Exception as e:
        print(json.dumps({"ok": False,
                          "reason": f"jax unavailable: {type(e).__name__}"}))
        return EXIT_UNAVAILABLE
    # an ambient site config can pin another platform OVER the env
    # var; the config API wins when set before the first device use,
    # so pin CPU both ways
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", FORCED_DEVICES)
    try:
        import xflow_tpu.train.step as _step
    except Exception as e:
        print(json.dumps({
            "ok": False,
            "reason": f"tree not importable from {root}: "
                      f"{type(e).__name__}: {e}"}))
        return EXIT_UNAVAILABLE
    got = os.path.realpath(getattr(_step, "__file__", "") or "")
    if not got.startswith(os.path.realpath(root) + os.sep):
        # a partial scratch tree (no package __init__) silently resolves
        # to the installed copy — lowering THAT would attribute the
        # wrong tree's semantics to this root
        print(json.dumps({
            "ok": False,
            "reason": f"tree under {root} is not an importable package "
                      f"(import resolved to {got})"}))
        return EXIT_UNAVAILABLE
    if args.probe:
        print(json.dumps({"ok": True}))
        return 0
    facts = extract_all(root)
    print(json.dumps(facts, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
