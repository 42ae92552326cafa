"""The seam where a state comes in from outside a step (train/engine.py
`Engine.place_state`, `Trainer._place_state`): the sorted engines pin the
layout of w, n, z on the step's way in and out, so whatever hands a
trainer its state — construction, the benchmark's `install_weights`, a
restored checkpoint, the fullshard engine's fallback step — the next
program meets it placed, once.

Off the TPU nothing is pinned (no tiled layout exists there), so these
tests steer the one rule, `engine.kernel_layout`, to the layout the CPU
client does know besides its default: column-major. Everything else is
the program's own path."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Layout

from xflow_tpu.config import Config, override
from xflow_tpu.parallel.mesh import make_mesh
from xflow_tpu.train.state import build_state
from xflow_tpu.train.trainer import Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COLUMN_MAJOR = Layout(major_to_minor=(1, 0), tiling=())
ROW_MAJOR = (0, 1)  # the CPU client's default for a rank-2 array


@pytest.fixture
def pinned(monkeypatch):
    from xflow_tpu.train import engine

    monkeypatch.setattr(engine, "kernel_layout", lambda device: COLUMN_MAJOR)


@pytest.fixture
def bench(monkeypatch):
    """The benchmark's harness (benchmark/lib), as tests/test_state_build.py takes it."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    yield os.path.join(ROOT, "benchmark")
    for name in [m for m in sys.modules if m == "lib" or m.startswith(("lib.", "reference"))]:
        sys.modules.pop(name, None)


def _orders(state) -> list:
    """major_to_minor of the table and optimizer leaves."""
    return [x.format.layout.major_to_minor for x in jax.tree.leaves((state.tables, state.opt_state))]


def _records(path) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f]


def _bits(state) -> list:
    return [np.asarray(x).view(np.uint32) for x in jax.tree.leaves(state)]


def _harness_run(bench, tmp_path, chips, name, **program_set):
    """`install_weights`, then three `fit()` calls, as benchmark/run.py's
    set-up drives them, at a small size. -> (trainer, metrics records)."""
    from lib import drive, weights
    from lib.traffic import load_traffic, make_run_data
    from reference import core as refcore

    with open(os.path.join(bench, "configs", "fm-v10-s25.json")) as f:
        cfg = json.load(f)
    cfg.update(log2_slots=14, batch_size=512, program_set=program_set)
    seed = 2**31 + 32
    data = make_run_data(
        str(tmp_path / f"data-{name}"), seed, cfg, load_traffic(bench, "text-zipf"), window=False
    )
    model = refcore.model_module(cfg["reference"])
    mpath = str(tmp_path / f"metrics-{name}.jsonl")
    trainer = drive.build_trainer(cfg, chips, data["train_prefix"], metrics_path=mpath)
    drive.install_weights(trainer, cfg, seed, model.width(cfg), weights.packed_table_fn)
    assert _orders(trainer.state) == [ROW_MAJOR] * 3  # as the harness made them
    drive.first_steps(
        trainer, cfg, seed, data, model.width(cfg), model.leaves(cfg), weights.packed_table_fn
    )
    return trainer, _records(mpath)


@pytest.mark.parametrize("chips", [1, 4])
def test_a_state_assigned_from_outside_is_placed_once(chips, pinned, bench, tmp_path):
    """The benchmark assigns `trainer.state` leaves it made itself, in the
    client's default layout. The next `fit()` places them — three leaves,
    one record, counted in that `fit()`'s final record — and no later
    `fit()` moves anything: each step hands the next its own output."""
    trainer, recs = _harness_run(bench, tmp_path, chips, "pinned")
    assert trainer.engine == ("sorted" if chips == 1 else "fullshard")
    assert _orders(trainer.state) == [COLUMN_MAJOR.major_to_minor] * 3
    (placed,) = [r for r in recs if r.get("kind") == "place_state"]
    leaves = jax.tree.leaves((trainer.state.tables, trainer.state.opt_state))
    assert placed["leaves_moved"] == 3
    assert placed["bytes"] == sum(x.nbytes for x in leaves)
    assert placed["dur_ms"] >= 0
    assert [r["state_leaves_placed"] for r in recs if r.get("final")] == [3, 0, 0]
    # after the build's record, before any step's
    kinds = [r.get("kind", "step") for r in recs]
    assert kinds.index("init_state") < kinds.index("place_state")
    assert not any(r.get("step") for r in recs[: kinds.index("place_state")])


@pytest.mark.parametrize("chips,recorder", [(1, True), (4, True), (1, False), (4, False)])
def test_a_program_that_hands_back_a_pinned_leaf_compiles_past_the_cache(
    chips, recorder, pinned, bench, tmp_path, monkeypatch
):
    """An executable read back from the persistent compile cache returns
    arrays that report the default layout whatever they are in
    (compile_cache.no_persistent_cache), so the programs that write the
    state — the one-device step; on a mesh the update, not the gradient
    program that only reads the table — and the placement's relayouts
    are compiled in every process (`compile_cache.past_cache`, with the
    compile recorder around it or without one); everything else is
    cached as before."""
    import contextlib

    from xflow_tpu import compile_cache
    from xflow_tpu.train import engine

    entered = []

    @contextlib.contextmanager
    def counted():
        entered.append(True)
        with real():
            yield

    real = compile_cache.no_persistent_cache
    monkeypatch.setattr(compile_cache, "no_persistent_cache", counted)
    monkeypatch.setattr(engine, "no_persistent_cache", counted)
    trainer, recs = _harness_run(
        bench, tmp_path, chips, "past", **({} if recorder else {"train.compile_metrics": False})
    )
    assert (trainer.compile_recorder is not None) == recorder
    compiles = {r["program"]: r.get("persistent_cache", True) for r in recs if r.get("kind") == "compile"}
    want = {"train_step": False} if chips == 1 else {
        "train_step.fullshard.fm": True, "update_step.fullshard.fm": False,
    }
    assert compiles == (want if recorder else {})
    # each writer's one compile, and the one placement (three leaves under one switch)
    assert len(entered) == 2
    assert _orders(trainer.state) == [COLUMN_MAJOR.major_to_minor] * 3


@pytest.mark.parametrize("chips", [1, 4])
def test_the_placed_run_computes_what_the_default_run_computes(chips, bench, tmp_path, monkeypatch):
    """A layout is where bytes sit, not what they are: three steps from
    the seed's weights leave the same bits in w, n, z pinned and not, on
    one device and through the mesh step's two programs."""
    from xflow_tpu.train import engine

    plain, recs = _harness_run(bench, tmp_path, chips, "plain")
    assert not [r for r in recs if r.get("kind") == "place_state"]
    assert [r["state_leaves_placed"] for r in recs if r.get("final")] == [0, 0, 0]
    monkeypatch.setattr(engine, "kernel_layout", lambda device: COLUMN_MAJOR)
    placed, _ = _harness_run(bench, tmp_path, chips, "placed")
    assert plain.engine == placed.engine == ("sorted" if chips == 1 else "fullshard")
    assert _orders(plain.state) == [ROW_MAJOR] * 3
    assert _orders(placed.state) == [COLUMN_MAJOR.major_to_minor] * 3
    for a, b in zip(_bits(plain.state), _bits(placed.state)):
        assert np.array_equal(a, b)


def test_blocks_past_the_cache_nest_and_take_turns():
    """The switch is the process's: a block inside a block changes
    nothing, blocks on two threads take turns, and the outermost exit
    puts back what the first entry found — whatever the order of exits."""
    import threading

    from xflow_tpu.compile_cache import no_persistent_cache

    flag = lambda: jax.config.jax_enable_compilation_cache
    was = flag()
    assert was  # JAX's default; the cache is on for every other compile
    with no_persistent_cache():
        assert not flag()
        with no_persistent_cache():
            assert not flag()
        assert not flag()  # the inner exit restores nothing
    assert flag() == was

    inside_a, leave_a, seen = threading.Event(), threading.Event(), {}

    def a():
        with no_persistent_cache():
            inside_a.set()
            leave_a.wait(10)
            seen["a"] = flag()

    def b():
        inside_a.wait(10)
        with no_persistent_cache():  # waits for a's exit
            seen["b_after_a_left"] = leave_a.is_set()
            seen["b"] = flag()

    threads = [threading.Thread(target=f) for f in (a, b)]
    for t in threads:
        t.start()
    inside_a.wait(10)
    threading.Event().wait(0.2)  # b is at the door by now
    leave_a.set()
    for t in threads:
        t.join(10)
    assert seen == {"a": False, "b": False, "b_after_a_left": True}
    assert flag() == was


def test_past_cache_compiles_once_a_signature_and_lowers_as_its_jit(monkeypatch):
    """Without a recorder the wrapper is what compiles: once for each
    signature, under the switch; and it lowers as the jit object does."""
    import contextlib

    from xflow_tpu import compile_cache

    entered = []

    @contextlib.contextmanager
    def counted():
        entered.append(jax.config.jax_enable_compilation_cache)
        with real():
            yield

    real = compile_cache.no_persistent_cache
    monkeypatch.setattr(compile_cache, "no_persistent_cache", counted)
    double = compile_cache.past_cache(jax.jit(lambda x, k=2.0: x * k))
    assert double.persistent_cache is False
    x = jnp.arange(6, dtype=jnp.float32)
    assert np.array_equal(double(x), 2 * np.arange(6)) and len(entered) == 1
    assert np.array_equal(double(x + 1), 2 * np.arange(1, 7)) and len(entered) == 1
    assert np.array_equal(double(x.reshape(2, 3)), 2 * np.arange(6).reshape(2, 3))
    assert len(entered) == 2  # a new shape: a new program
    assert "stablehlo.multiply" in double.lower(x).as_text()
    assert len(entered) == 2  # lowering compiles nothing
    assert jax.config.jax_enable_compilation_cache


def _cfg(model="fm", mesh=False, **extra):
    over = {
        "model.name": model, "data.log2_slots": 14, "data.batch_size": 64,
        "data.max_nnz": 8, "model.num_fields": 5, "train.pred_dump": False, **extra,
    }
    if mesh:
        over.update({"mesh.data": 2, "mesh.table": 2})
    return override(Config(), **over)


@pytest.mark.parametrize("model,mesh,moves", [
    ("fm", False, 3), ("fm", True, 3), ("mvm", False, 3), ("ffm", True, 3),
    ("lr", False, 0), ("lr", True, 0),
])
def test_place_state_moves_a_leaf_once_and_only_a_packed_one(model, mesh, moves, pinned):
    """By engine and rank: the sorted engines' rank-2 leaves go to the
    kernels' layout, LR's 1-D leaves and the row-major engines' state
    stay where they are; a second call compares and moves nothing."""
    cfg = _cfg(model, mesh, **({"model.v_dim": 3} if model == "ffm" else {}))
    t = Trainer(cfg, mesh=make_mesh(cfg, devices=jax.devices()[:4]) if mesh else None)
    eng = t._engine
    assert eng.name == {("fm", False): "sorted", ("mvm", False): "sorted",
                        ("fm", True): "fullshard", ("ffm", True): "fullshard",
                        ("lr", False): "row_major", ("lr", True): "gspmd"}[(model, mesh)]
    packed = [x for x in jax.tree.leaves(t.state) if x.ndim == 2]
    state, moved, nbytes = eng.place_state(t.state)
    assert (moved, nbytes) == (moves, sum(x.nbytes for x in packed) if moves else 0)
    assert len(packed) == moves and all(x.is_deleted() for x in packed)  # nothing old is left
    again, moved, nbytes = eng.place_state(state)
    assert (moved, nbytes) == (0, 0)
    assert all(a is b for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(state)))
    if not moves:
        assert eng.state_formats(state) is None
        assert all(a is b for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(t.state)))


def test_nothing_is_pinned_off_the_tpu():
    """The rule itself, unsteered: a CPU has no tiled layout, so the
    sorted engine names no format and its step compiles as it always did."""
    t = Trainer(_cfg("fm"))
    assert t.engine == "sorted" and t._engine.state_formats(t.state) is None
    assert t._engine.place_state(t.state) == (t.state, 0, 0)


def _one_batch_shard(tmp_path, cfg, rows):
    rng = np.random.default_rng(7)
    path = tmp_path / "train-00000"
    with open(path, "w") as f:
        for _ in range(rows):
            feats = " ".join(
                f"{k}:{rng.integers(0, 4000)}:1" for k in range(cfg.model.num_fields)
            )
            f.write(f"{rng.integers(0, 2)} {feats}\n")
    return str(tmp_path / "train")


def test_save_restore_one_step_equals_the_uninterrupted_step(pinned, tmp_path, monkeypatch):
    """A restored checkpoint is a state from outside a step: it comes
    back in the default layout, `fit()` places it, and the step after it
    leaves the bits the uninterrupted run leaves."""
    monkeypatch.chdir(tmp_path)
    base = _cfg("fm")
    prefix = _one_batch_shard(tmp_path, base, base.data.batch_size)
    two = Trainer(override(base, **{"data.train_path": prefix, "train.epochs": 2}))
    assert two.fit().steps == 2
    ck = {"data.train_path": prefix, "train.checkpoint_dir": str(tmp_path / "ck")}
    one = Trainer(override(base, **ck, **{"train.epochs": 1}))
    assert one.fit().steps == 1
    back = Trainer(override(base, **ck, **{"train.epochs": 1}))
    assert back.maybe_restore() and int(back.state.step) == 1
    # the weights only: its one pass re-reads the shard, as the
    # uninterrupted run's second epoch does
    back._resume_data_state = None
    assert _orders(back.state) == [ROW_MAJOR] * 3  # the checkpoint reader knows no layout
    res = back.fit()
    assert (res.steps, res.state_leaves_placed) == (1, 3)
    assert _orders(back.state) == [COLUMN_MAJOR.major_to_minor] * 3
    for a, b in zip(_bits(two.state), _bits(back.state)):
        assert np.array_equal(a, b)
    # evaluate() reads the same placed table through the gather kernel
    shard = prefix + "-00000"
    assert back.evaluate(test_path=shard, dump=False) == two.evaluate(test_path=shard, dump=False)


def test_the_fullshard_fallback_hands_back_a_state_the_next_step_accepts(pinned):
    """A batch too skewed for the slack runs the GSPMD step, compiled
    with the engine's formats in and out: the state it returns is the one
    the fullshard step takes, and the other way round."""
    from xflow_tpu.data.schema import SparseBatch
    from xflow_tpu.models import get_model
    from xflow_tpu.optim import get_optimizer
    from xflow_tpu.train.engine import resolve_engine

    cfg = _cfg("fm", True, **{"data.fullshard_slack": 1.0, "data.batch_size": 2048})
    mesh = make_mesh(cfg, devices=jax.devices()[:4])
    model, opt = get_model("fm"), get_optimizer("ftrl")
    eng = resolve_engine(cfg, mesh, model, opt, None)
    state, moved, _ = eng.place_state(build_state(model, opt, cfg, eng.state_shardings))
    assert moved == 3

    def batch(slots):
        B, F = slots.shape
        return SparseBatch(
            slots=slots.astype(np.int32),
            fields=np.tile(np.arange(F, dtype=np.int32) % 5, (B, 1)),
            mask=np.ones((B, F), np.float32),
            labels=np.zeros((B,), np.float32),
            row_mask=np.ones((B,), np.float32),
        )

    rng = np.random.default_rng(0)
    uniform = eng.batch_arrays(batch(rng.integers(0, cfg.num_slots, (2048, 8))))
    skewed = eng.batch_arrays(batch(np.zeros((2048, 8))))
    assert not eng.fell_back(uniform) and eng.fell_back(skewed)
    for arrays in (uniform, skewed, uniform, skewed):
        state, m = eng.train_step(state, eng.shard_batch(arrays))
        assert np.isfinite(float(m["loss"]))
        assert _orders(state) == [COLUMN_MAJOR.major_to_minor] * 3
        assert eng.place_state(state)[1] == 0
    assert int(state.step) == 4


def test_the_recorder_keeps_a_program_per_layout(pinned):
    """A jit with no layout of its own (eval, the harness's reductions)
    compiles for its argument's: the recorder's cache must not hand the
    executable of one layout an array in the other."""
    from jax.experimental.layout import Format

    from xflow_tpu.telemetry import CompileRecorder

    rec = CompileRecorder()
    total = rec.wrap("total", jax.jit(lambda x: x.sum()))
    x = jax.device_put(jnp.arange(24, dtype=jnp.float32).reshape(4, 6), jax.devices()[0])
    y = jax.device_put(x, Format(COLUMN_MAJOR, x.sharding))
    assert float(total(x)) == float(total(y)) == 276.0
    assert float(total(x)) == float(total(y)) == 276.0
    assert len(rec.records) == 2 and rec.recompiles == 0
    assert rec.records[0]["sig"] != rec.records[1]["sig"]
