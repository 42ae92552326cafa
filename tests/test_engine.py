"""train/engine.py: which engine a (config, mesh) resolves to, the
refusals of forced choices it cannot run (exact texts), and the seam
the trainer calls — one table instead of `t._mesh_engine == ...`
asserts scattered through larger tests."""

import ast
import os

import jax
import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.data.schema import SparseBatch
from xflow_tpu.models import get_model
from xflow_tpu.optim import get_optimizer
from xflow_tpu.parallel.mesh import make_mesh
from xflow_tpu.train.engine import ENGINE_MODULES, Engine, resolve_engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODELS = {
    "lr": {"model.name": "lr"},
    "fm": {"model.name": "fm"},
    "fm_unfused": {"model.name": "fm", "model.fm_fused": False},
    "mvm": {"model.name": "mvm"},
    "ffm": {"model.name": "ffm", "model.v_dim": 3},
}
PLACEMENTS = {"one_device": None, "mesh2x2": (2, 2), "mesh4x1": (4, 1)}
SORTABLE = ("fm", "mvm", "ffm")

ON_NEEDS_A_SORTABLE_MODEL = (
    "sorted_layout=on requires model.name=fm with model.fm_fused=true, "
    "model.name=mvm, or model.name=ffm; got model={name} fm_fused={fused}"
)
FULLSHARD_NO_LR = (
    "fullshard layout supports fused FM, MVM, and FFM (LR keeps the "
    "GSPMD row-major path); got model=lr"
)
FULLSHARD_UNFUSED_FM = "fullshard FM needs model.fm_fused=true (one table)"


def _cfg(model="fm", placement="one_device", **extra):
    over = {
        "data.log2_slots": 14, "data.batch_size": 64, "data.max_nnz": 8,
        "model.num_fields": 5, **MODELS[model], **extra,
    }
    shape = PLACEMENTS[placement]
    if shape is not None:
        over.update({"mesh.data": shape[0], "mesh.table": shape[1]})
    return override(Config(), **over)


def _resolve(cfg, placement) -> Engine:
    mesh = None
    if PLACEMENTS[placement] is not None:
        mesh = make_mesh(cfg, devices=jax.devices()[:4])
    return resolve_engine(
        cfg, mesh, get_model(cfg.model.name), get_optimizer(cfg.optim.name), None
    )


def _expected(model, placement, layout):
    """-> an engine name, or the ValueError text of the refusal."""
    sortable = model in SORTABLE
    if placement == "one_device":
        if layout == "on" and not sortable:
            return ON_NEEDS_A_SORTABLE_MODEL.format(
                name=MODELS[model]["model.name"], fused=model != "fm_unfused"
            )
        return "sorted" if sortable and layout != "off" else "row_major"
    if layout == "on" and not sortable:
        return FULLSHARD_NO_LR if model == "lr" else FULLSHARD_UNFUSED_FM
    return "fullshard" if sortable and layout != "off" else "gspmd"


@pytest.mark.parametrize("layout", ["auto", "on", "off"])
@pytest.mark.parametrize("placement", list(PLACEMENTS))
@pytest.mark.parametrize("model", list(MODELS))
def test_engine_table(model, placement, layout):
    cfg = _cfg(model, placement, **{"data.sorted_layout": layout})
    want = _expected(model, placement, layout)
    if want not in ENGINE_MODULES:
        with pytest.raises(ValueError) as e:
            _resolve(cfg, placement)
        assert str(e.value) == want
        return
    eng = _resolve(cfg, placement)
    assert eng.name == want
    # the sorted layouts plan on the host, the row-major ones do not
    assert (eng.planner in ("native", "python")) == (want in ("sorted", "fullshard"))
    assert (eng.planner is None) == (want in ("row_major", "gspmd"))
    # one device builds its state where it is; a mesh gets shardings
    assert (eng.state_shardings is None) == (placement == "one_device")


REFUSALS = {
    "on_needs_whole_windows": (
        "one_device", {"data.sorted_layout": "on", "data.log2_slots": 10},
        "sorted_layout=on needs num_slots divisible by 2048; got 2^10",
    ),
    "mesh_on_needs_a_window_a_device": (
        "mesh2x2", {"data.sorted_layout": "on", "data.log2_slots": 12},
        "fullshard layout needs num_slots (2^12) divisible by "
        "data*table*WINDOW = 2*2*2048 (each device owns whole windows)",
    ),
    "fused_scatter_on_under_a_mesh": (
        "mesh2x2", {"optim.fused_scatter": "on"},
        "optim.fused_scatter=on requires the single-device step; mesh "
        "engines run the two-pass form — use auto (fuses where eligible) "
        "or off",
    ),
    "fused_scatter_on_under_a_gspmd_mesh": (
        "mesh4x1", {"optim.fused_scatter": "on", "data.sorted_layout": "off"},
        "optim.fused_scatter=on requires the single-device step; mesh "
        "engines run the two-pass form — use auto (fuses where eligible) "
        "or off",
    ),
    "mesh_on_batch_not_divisible": (
        "mesh4x1", {"data.sorted_layout": "on", "data.batch_size": 63},
        "per-process batch_size 63 not divisible by the local data-shard "
        "count 4",
    ),
    "mesh_on_sub_batches_conflict": (
        "mesh2x2", {"data.sorted_layout": "on", "data.sorted_sub_batches": 8},
        "data.sorted_sub_batches=8 conflicts with the fullshard plan count "
        "(= 2 per process); leave it 0",
    ),
    "mesh_on_slack_under_one": (
        "mesh2x2", {"data.sorted_layout": "on", "data.fullshard_slack": 0.5},
        "data.fullshard_slack=0.5 < 1 cannot hold even perfectly uniform "
        "occupancy",
    ),
    "dedup_value": (
        "one_device", {"data.dedup": "on"},
        "data.dedup='on': expected auto|off",
    ),
}


@pytest.mark.parametrize("case", list(REFUSALS))
def test_engine_refusals_say_why(case):
    placement, extra, text = REFUSALS[case]
    with pytest.raises(ValueError) as e:
        _resolve(_cfg("fm", placement, **extra), placement)
    assert str(e.value) == text


@pytest.mark.parametrize("placement,extra,want", [
    # auto never refuses: what the sorted layouts cannot run stays row-major
    ("one_device", {"data.log2_slots": 10}, "row_major"),
    ("mesh2x2", {"data.log2_slots": 12}, "gspmd"),
    ("mesh4x1", {"data.batch_size": 63}, "gspmd"),
    ("mesh2x2", {"data.fullshard_slack": 0.5}, "gspmd"),
])
def test_auto_falls_back_instead_of_refusing(placement, extra, want):
    assert _resolve(_cfg("fm", placement, **extra), placement).name == want


def test_the_replicated_engines_switch_is_an_unknown_key():
    with pytest.raises(KeyError, match="sorted_mesh"):
        override(Config(), **{"data.sorted_mesh": "replicated"})


def _batch(slots):
    B, F = slots.shape
    return SparseBatch(
        slots=slots.astype(np.int32),
        fields=np.tile(np.arange(F, dtype=np.int32) % 5, (B, 1)),
        mask=np.ones((B, F), np.float32),
        labels=np.zeros((B,), np.float32),
        row_mask=np.ones((B,), np.float32),
    )


def test_fullshard_batch_too_skewed_for_the_slack_falls_back(capsys):
    """Every occurrence of the batch in one owner block: beyond any
    slack near 1, so `batch_arrays` hands back row-major arrays for the
    GSPMD step and `fell_back` says so; a uniform batch keeps the plan."""
    # 2048 rows: a block's buffer holds 2,560 of a shard's 8,192 occurrences
    cfg = _cfg("fm", "mesh2x2", **{"data.fullshard_slack": 1.0,
                                   "data.batch_size": 2048})
    eng = _resolve(cfg, "mesh2x2")
    rng = np.random.default_rng(0)
    uniform = eng.batch_arrays(_batch(rng.integers(0, cfg.num_slots, (2048, 8))))
    assert "fs_slots" in uniform and "slots" not in uniform
    assert not eng.fell_back(uniform)
    skewed = eng.batch_arrays(_batch(np.zeros((2048, 8))))
    assert "fs_slots" not in skewed
    assert {"slots", "mask", "labels", "row_mask"} <= set(skewed)
    assert eng.fell_back(skewed)
    # one process: nothing to agree on, the arrays pass through
    assert eng.agree(None, skewed) is skewed
    err = capsys.readouterr().err
    assert err.count("falling back to the GSPMD row-major step") == 1


def test_only_the_fullshard_engine_falls_back():
    for placement, layout in (("one_device", "auto"), ("one_device", "off"),
                              ("mesh2x2", "off")):
        cfg = _cfg("fm", placement, **{"data.sorted_layout": layout})
        eng = _resolve(cfg, placement)
        arrays = eng.batch_arrays(_batch(np.zeros((64, 8))))
        assert not eng.fell_back(arrays)
        assert eng.agree(None, arrays) is arrays


def _imports(path):
    tree = ast.parse(open(path).read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield node.module or ""


def test_trainer_does_not_know_the_engines():
    """The trainer reaches the step builders through train/engine.py
    only: no import of a sorted engine's module, at any depth of the
    file (function-level imports included)."""
    seen = set(_imports(os.path.join(ROOT, "xflow_tpu", "train", "trainer.py")))
    assert "xflow_tpu.train.engine" in seen
    banned = {"xflow_tpu.parallel.sorted_fullshard", "xflow_tpu.ops.sorted_table",
              "xflow_tpu.parallel.train_step"}
    assert not seen & banned


def test_the_analysis_tier_reads_the_engine_list_from_the_one_place():
    from xflow_tpu.analysis.astutil import engine_modules
    from xflow_tpu.analysis.ir import PROGRAMS
    from xflow_tpu.analysis.passes.recompile import RECORDER_SCOPED
    from xflow_tpu.analysis.passes.sharding_contract import ENGINE_MODULES as CHECKED

    assert engine_modules() == ENGINE_MODULES
    assert set(CHECKED) == set(ENGINE_MODULES.values())
    assert set(ENGINE_MODULES.values()) <= set(RECORDER_SCOPED)
    assert {p[1] for p in PROGRAMS} == set(ENGINE_MODULES.values())
    for rel in CHECKED:
        assert os.path.exists(os.path.join(ROOT, rel)), rel
