"""The step's phase map (telemetry.PHASE_LABELS, `op_phases`,
docs/OBSERVABILITY.md "Step phases"): every engine the benchmark runs
compiles its step through the CompileRecorder, and the record's
`op_scopes` gives every operation a trace can show ONE label of the one
vocabulary, or "" — the same six phases with the same meaning in every
engine. Also here: the rule that tells a phase from its transpose, the
cap, and the persistent compile cache, which must not hand back
another commit's labels.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.telemetry import PHASE_LABELS, CompileRecorder, Registry, op_phases, phase_of_path

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASE_OF = dict(PHASE_LABELS)

# engine -> (config overrides, mesh?, the phases its step has).
# `scatter` is an operation of its own where the gather's transpose is:
# XLA's scatter-add (row-major; the GSPMD step) and the two-pass scatter
# (fullshard) — not in the fused sorted steps, whose scatter is the
# fused kernel's first half, inside `update`. `exchange` only on a mesh.
# Off the TPU the sorted kernels are their XLA stand-ins, and XLA's CPU
# backend fuses FFM's stand-in gather into the placement's gather that
# reads it (a fusion is booked to its root's phase): that one case is
# left out here and held by tests/test_tpu_compile.py, on the chip's own
# compiler, where the gather is a Mosaic call.
ENGINES = {
    "row_major_lr": ({"model.name": "lr", "data.sorted_layout": "off"}, False,
                     {"gather", "rows", "scatter", "update"}),
    "sorted_fm": ({"model.name": "fm"}, False, {"gather", "rows", "update"}),
    "fullshard_fm": ({"model.name": "fm", "mesh.data": 2, "mesh.table": 2}, True,
                     {"exchange", "gather", "rows", "scatter", "update"}),
    "ffm_aligned": ({"model.name": "ffm", "model.v_dim": 4}, False, {"rows", "update"}),
    "gspmd_lr": ({"model.name": "lr", "mesh.data": 2, "mesh.table": 2}, True,
                 {"exchange", "gather", "rows", "scatter", "update"}),
}
CASES = [(e, p) for e in sorted(ENGINES) for p in ("exchange", "gather", "rows", "scatter", "update")
         if (e, p) != ("ffm_aligned", "gather")]


def _records(engine, tmp_path, **more):
    """The compile records of one short `fit()` on `engine` (`more`:
    config overrides beside the engine's)."""
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.train.trainer import Trainer

    extra, meshed, _ = ENGINES[engine]
    rows = [f"{i % 2}\t" + " ".join(f"{f}:{100 * f + (i * 7 + f) % 13}:1" for f in range(4)) + "\n"
            for i in range(64)]
    with open(tmp_path / "train-00000", "w") as f:
        f.writelines(rows)
    cfg = override(Config(), **{
        "model.num_fields": 4, "data.max_nnz": 4, "data.log2_slots": 14, "data.batch_size": 32,
        "data.train_path": str(tmp_path / "train"), "train.epochs": 1, "train.pred_dump": False,
        **extra, **more,
    })
    trainer = Trainer(cfg, mesh=make_mesh(cfg, devices=jax.devices()[:4]) if meshed else None)
    assert trainer.fit().steps == 2
    return trainer, [r for r in trainer.compile_recorder.records if "step" in r["program"]]


@pytest.fixture(scope="module")
def compiled(tmp_path_factory):
    cache: dict = {}

    def get(engine):
        if engine not in cache:
            cache[engine] = _records(engine, tmp_path_factory.mktemp(engine))
        return cache[engine]

    return get


def _labels(records) -> dict:
    """{label: [operations]} over a step's programs."""
    out: dict = {}
    for rec in records:
        for op, label in rec["op_scopes"].items():
            out.setdefault(label, []).append(op)
    return out


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_step_is_the_engine_it_is_meant_to_be(engine, compiled):
    trainer, records = compiled(engine)
    want = {"row_major_lr": "row_major", "sorted_fm": "sorted", "fullshard_fm": "fullshard",
            "ffm_aligned": "sorted", "gspmd_lr": "gspmd"}[engine]
    assert trainer.engine == want
    programs = sorted(r["program"] for r in records)
    if engine == "fullshard_fm":
        assert programs == ["train_step.fullshard.fm", "update_step.fullshard.fm"]
    else:
        assert len(programs) == 1
    assert all(r["analysis_s"] >= 0 and "op_scopes_dropped" not in r for r in records)


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_every_label_is_of_the_one_vocabulary(engine, compiled):
    labels = _labels(compiled(engine)[1])
    assert set(labels) <= set(PHASE_OF) | {""}, sorted(labels)
    assert "health" not in labels  # train.health_metrics is off by default


@pytest.mark.parametrize("engine,phase", CASES)
def test_phase_stands_exactly_where_the_table_says(engine, phase, compiled):
    by_phase: dict = {}
    for label, ops in _labels(compiled(engine)[1]).items():
        by_phase.setdefault(PHASE_OF.get(label, ""), []).extend(ops)
    assert bool(by_phase.get(phase)) == (phase in ENGINES[engine][2]), (engine, phase, sorted(by_phase))


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_few_operations_are_left_without_a_phase(engine, compiled):
    labels = _labels(compiled(engine)[1])
    total = sum(len(ops) for ops in labels.values())
    assert len(labels.get("", [])) < 0.05 * total, (labels.get(""), total)


def test_ffm_keeps_its_labels_inside_rows(compiled):
    labels = _labels(compiled("ffm_aligned")[1])
    assert labels.get("ffm_place") and labels.get("ffm_pair")
    assert PHASE_OF["ffm_place"] == PHASE_OF["ffm_pair"] == "rows"
    assert labels.get("scatter_optimizer") and PHASE_OF["scatter_optimizer"] == "update"


def test_health_has_a_phase_when_it_is_on(tmp_path):
    labels = _labels(_records("row_major_lr", tmp_path, **{"train.health_metrics": "norms"})[1])
    assert labels.get("health") and set(labels) <= set(PHASE_OF) | {""}


@pytest.mark.parametrize("path,want", [
    # plain autodiff wraps the outermost scope inside the transform
    ("jit(train_step)/jvp(rows)/gather/gather", "gather"),
    ("jit(train_step)/transpose(jvp(rows))/gather/scatter-add", "scatter"),
    ("jit(train_step)/jvp(gather)/gather", "gather"),
    ("jit(train_step)/transpose(jvp(gather))/scatter-add", "scatter"),
    # inside a shard_map the wrapper stands before it
    ("jit(grad_part)/jvp()/shard_map/rows/gather/pallas_call", "gather"),
    ("jit(grad_part)/transpose(jvp())/shard_map/rows/gather/pallas_call", "scatter"),
    # every other label is its own transpose
    ("jit(train_step)/rows/transpose(jvp())/mul", "rows"),
    ("jit(train_step)/rows/transpose(rows)/jvp(ffm_place)/gather", "ffm_place"),
    ("jit(grad_part)/transpose(jvp())/shard_map/rows/exchange/all_gather", "exchange"),
    ("jit(train_step)/update/scatter_optimizer/pallas_call", "scatter_optimizer"),
    # the primitive is never a label, and a path may name none
    ("jit(train_step)/rows/transpose(rows)/jvp(jit(_take))/gather", "rows"),
    ("jit(train_step)/add", ""),
    ("jit(train_step)/jit(_where)/select_n", ""),
])
def test_transpose_rule(path, want):
    assert phase_of_path(path) == want


HLO = """\
HloModule jit_step, is_scheduled=true

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %inner.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(step)/rows/add"}
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %x.2 = f32[8]{0} get-tuple-element(%t), index=1
  %fusion.7 = f32[8]{0} fusion(%x.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/jvp(rows)/gather/while/body/gather"}
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%x.2, %fusion.7)
}

ENTRY %main (a: f32[8], b: f32[8]) -> (f32[8], f32[8]) {
  %a = f32[8]{0} parameter(0)
  %b = f32[8]{0} parameter(1), metadata={op_name="state.tables['w']"}
  %copy.1 = f32[8]{0:T(128)} copy(%a)
  %fusion.1 = (f32[8]{0:T(8,128)S(1)}, f32[8]{0}) fusion(%copy.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/jvp(rows)/gather/gather"}
  %gte.1 = f32[8]{0} get-tuple-element(%fusion.1), index=0
  %copy.2 = f32[8]{0} copy(%gte.1)
  %add.3 = f32[8]{0} add(%copy.2, %b), metadata={op_name="jit(step)/transpose(jvp(rows))/mul"}
  %fusion.2 = f32[8]{0} fusion(%copy.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/update/mul"}
  %while.1 = (s32[], f32[8]{0}) while(%fusion.1), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(rows)/gather/while"}
  %copy.3 = f32[8]{0} copy(%b), metadata={op_name="state.tables['w']"}
  %all-reduce.1 = f32[8]{0} all-reduce(%copy.3), to_apply=%sum, metadata={op_name="jit(step)/update/reduce_and"}
  %mul.9 = f32[8]{0} multiply(%add.3, %add.3), metadata={op_name="jit(step)/mul"}
  %bitcast.4 = f32[8]{0} bitcast(%mul.9)
  ROOT %tuple.1 = (f32[8]{0}, f32[8]{0}) tuple(%fusion.2, %all-reduce.1)
}
"""


def test_map_covers_what_a_trace_can_show_and_inherits_through_layout_copies():
    phases = op_phases(HLO)
    assert phases == {
        "copy.1": "gather",  # no path of its own: its one consumer's
        "fusion.1": "gather",
        "copy.2": "gather",  # consumers disagree (rows, update): its producer's, through the view
        "add.3": "rows",
        "fusion.2": "update",
        "while.1": "gather",
        "fusion.7": "gather",  # a loop body's operations run as operations of their own
        "copy.3": "exchange",  # a parameter's name is no path
        "all-reduce.1": "exchange",  # a collective, whatever scope it stands in
        "mul.9": "",  # a path that names no phase: the program gave it none
    }


class FakeJitted:
    """The .lower().compile() seam without jax."""

    def __init__(self, compiled):
        self._compiled = compiled

    def lower(self, *args, **kwargs):
        return self

    def compile(self):
        return self._compiled


def test_capped_record_says_what_it_dropped(monkeypatch):
    class Compiled:
        def as_text(self):
            return HLO

    rec = CompileRecorder(registry=Registry())
    monkeypatch.setattr(CompileRecorder, "OP_SCOPES_CAP", 7)
    module, scopes, dropped, kernels = rec._op_scopes(Compiled())
    assert module == "jit_step" and kernels == 0
    assert dropped == 3 and len(scopes) == 7
    # what a trace shows by name is kept first: fusions, copies, collectives
    assert set(scopes) == {"copy.1", "fusion.1", "copy.2", "fusion.2", "fusion.7", "copy.3", "all-reduce.1"}
    rec.record("train_step", FakeJitted(Compiled()))
    assert rec.records[0]["op_scopes_dropped"] == 3 and len(rec.records[0]["op_scopes"]) == 7


CACHE_PROBE = """
import json, sys
import jax
from xflow_tpu.compile_cache import enable_compile_cache
from xflow_tpu.telemetry import CompileRecorder, Registry
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
label = sys.argv[1]
def step(x):
    with jax.named_scope(label):
        return (x * 2.0 + 1.0).sum()
def run():
    rec = CompileRecorder(registry=Registry())
    rec.wrap("train_step", jax.jit(step))(jax.numpy.ones((64, 64)))
    r = rec.records[0]
    print(json.dumps({"cache_hit": r["cache_hit"], "labels": sorted(set(r["op_scopes"].values()))}))
def from_another_entry_point():
    run()
from_another_entry_point() if sys.argv[2:] else run()
"""


def test_persistent_cache_never_hands_back_another_commits_labels(tmp_path):
    """JAX leaves metadata out of the persistent cache's key by default,
    so a program that differs from a cached one by its scopes alone would
    be READ from the cache with the other commit's `op_name` paths in
    its text. `enable_compile_cache` puts the metadata into the key: the
    same program with the same scopes is a hit, with other scopes a
    compile, and its record carries its own labels. A location in the key
    is the operation's innermost frame, not the call stack that led to
    it: the same program reached through another caller is a hit too."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))

    def run(*argv):
        r = subprocess.run([sys.executable, "-c", CACHE_PROBE, *argv], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    first, again, deeper, other = run("rows"), run("rows"), run("rows", "deeper"), run("update")
    assert first == {"cache_hit": False, "labels": ["rows"]}
    assert again == {"cache_hit": True, "labels": ["rows"]}  # a deserialized executable's text has its metadata
    assert deeper == again
    assert other == {"cache_hit": False, "labels": ["update"]}
