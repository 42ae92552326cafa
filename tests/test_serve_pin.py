"""The served table's layout, placed once at load (serve/runner.py
`pinned_table_formats`, `ServeRunner._pin`): on a TPU a rank-2 table is
pinned in the layout its row gather reads, and the predict rungs are
compiled for it, so no call re-lays the table.

Off the TPU nothing is pinned (no tiled layout exists there), so these
tests steer the one rule, `engine.kernel_layout`, to the layout the CPU
client does know besides its default: column-major, as
tests/test_place_state.py does for training. Everything else is the
runner's own path."""

import numpy as np
import pytest
from jax.experimental.layout import Layout

from xflow_tpu.config import Config, override
from xflow_tpu.serve.runner import ServeRunner
from xflow_tpu.telemetry import CompileRecorder
from xflow_tpu.train.checkpoint import write_flat

COLUMN_MAJOR = Layout(major_to_minor=(1, 0), tiling=())
LOG2_SLOTS, K = 10, 11  # FM's row: w and v_dim 10
ROWS = [f"{f}:{f * 7 + i % 13}:1 {f + 1}:{i}:1 4:{i % 5}:1" for i in range(40) for f in (0, 2)]


@pytest.fixture
def pinned(monkeypatch):
    from xflow_tpu.train import engine

    monkeypatch.setattr(engine, "kernel_layout", lambda device: COLUMN_MAJOR)


def _cfg(ckpt_dir, model="fm", **extra):
    return override(Config(), **{
        "model.name": model,
        "model.num_fields": 5,
        "data.log2_slots": LOG2_SLOTS,
        "data.max_nnz": 8,
        "train.checkpoint_dir": str(ckpt_dir),
        "serve.max_batch": 32,
        **extra,
    })


def _commit(ckpt_dir, step, model="fm"):
    """A committed tables-only checkpoint at `step`, its values drawn
    from the step, in the logical layout every npz checkpoint has."""
    rng = np.random.default_rng(step)
    slots = 1 << LOG2_SLOTS
    if model == "fm":
        tables = {"tables/wv": rng.normal(0, 0.3, (slots, K)).astype(np.float32)}
    else:
        tables = {"tables/w": rng.normal(0, 0.3, (slots,)).astype(np.float32)}
    write_flat(str(ckpt_dir), {**tables, "step": np.asarray(step, np.int32)}, step)


def _order(x) -> tuple:
    return x.format.layout.major_to_minor


def _bits(p) -> np.ndarray:
    return np.asarray(p, np.float32).view(np.uint32)


class _Sink(list):
    """What `span_sink` takes: anything with .append(dict)."""


def test_load_pins_the_rank2_table(pinned, tmp_path):
    _commit(tmp_path, 1)
    r = ServeRunner(_cfg(tmp_path))
    gen = r.load()
    assert _order(gen.tables["wv"]) == COLUMN_MAJOR.major_to_minor
    assert gen.tables["wv"].shape == ((1 << LOG2_SLOTS) // 8, 8 * K)


def test_pinned_answers_are_the_unpinned_answers_bit_for_bit(tmp_path, monkeypatch):
    from xflow_tpu.train import engine

    _commit(tmp_path, 1)
    plain = ServeRunner(_cfg(tmp_path))  # formats decided here: none
    monkeypatch.setattr(engine, "kernel_layout", lambda device: COLUMN_MAJOR)
    pin = ServeRunner(_cfg(tmp_path))
    plain.load(), pin.load()
    assert _order(plain.generation.tables["wv"]) == (0, 1)
    assert _order(pin.generation.tables["wv"]) == (1, 0)
    p_plain, _ = plain.predict_rows(ROWS)
    p_pin, _ = pin.predict_rows(ROWS)
    assert np.array_equal(_bits(p_plain), _bits(p_pin))
    assert np.ptp(p_pin) > 0.05  # the rows' answers differ: a wrong row would show


def test_a_reload_is_pinned_before_the_swap_and_the_old_generation_answers_until_then(
    pinned, tmp_path
):
    _commit(tmp_path, 1)
    r = ServeRunner(_cfg(tmp_path))
    old = r.load()
    before, _ = r.predict_rows(ROWS)
    _commit(tmp_path, 2)
    seen = {}
    pin = r._pin

    def watched(tables):
        # restored, not yet swapped in: requests still meet generation 1
        seen["answer"], seen["gen"] = r.predict_rows(ROWS)
        out = pin(tables)
        seen["order"] = _order(out[0]["wv"])
        seen["serving"] = r.generation
        return out

    r._pin = watched
    new = r.maybe_reload()
    assert new is not None and (new.gen, new.step) == (2, 2)
    assert seen["gen"] is old and seen["serving"] is old
    assert np.array_equal(_bits(seen["answer"]), _bits(before))
    assert seen["order"] == COLUMN_MAJOR.major_to_minor
    assert r.generation is new and _order(new.tables["wv"]) == COLUMN_MAJOR.major_to_minor
    after, gen = r.predict_rows(ROWS)
    assert gen is new and not np.array_equal(_bits(after), _bits(before))


def test_each_rung_compiles_once_through_warmup_traffic_and_a_reload(pinned, tmp_path):
    _commit(tmp_path, 1)
    rec = CompileRecorder()
    r = ServeRunner(_cfg(tmp_path, **{"serve.ladder": "8,16"}), recorder=rec)
    r.load()
    assert r.warmup() == 3
    for i in range(50):
        r.predict_rows(ROWS[: 1 + i % 30])
    _commit(tmp_path, 2)
    assert r.maybe_reload() is not None
    for i in range(5):
        r.predict_rows(ROWS[: 1 + i])
    names = sorted(x["program"] for x in rec.records)
    assert names == ["predict.serve.b16", "predict.serve.b32", "predict.serve.b8"]
    assert rec.recompiles == 0
    # cache-safe: every output is in the default layout; the record
    # names the table's pinned layout
    assert all(x["persistent_cache"] is True for x in rec.records)
    assert all(x["table_layouts"] == {"wv": str(COLUMN_MAJOR)} for x in rec.records)


@pytest.mark.parametrize("case", ["lr_1d_table", "mesh_runner", "no_kernel_layout"])
def test_what_the_rule_leaves_unpinned(case, tmp_path, monkeypatch):
    """LR's 1-D table and the mesh runner keep their placement under the
    rule; without a kernel layout (every CPU) nothing is pinned. The
    compile records then carry neither pinned field."""
    import jax

    from xflow_tpu.train import engine

    if case != "no_kernel_layout":
        monkeypatch.setattr(engine, "kernel_layout", lambda device: COLUMN_MAJOR)
    model = "lr" if case == "lr_1d_table" else "fm"
    _commit(tmp_path, 1, model)
    cfg = _cfg(tmp_path, model)
    rec = CompileRecorder()
    mesh = None
    if case == "mesh_runner":
        if len(jax.devices()) < 2:
            pytest.skip("single-device jax build")
        from xflow_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(cfg)
    r = ServeRunner(cfg, mesh=mesh, recorder=rec)
    sink = _Sink()
    r.span_sink = sink
    gen = r.load()
    (table,) = gen.tables.values()
    default = jax.device_put(np.zeros(table.shape, table.dtype))
    assert _order(table) == _order(default)
    assert r._table_formats is None
    (span,) = sink
    assert (span["pinned_leaves"], span["pinned_bytes"]) == (0, 0)
    p, _ = r.predict_rows(ROWS[:20])
    assert p.shape == (20,) and np.all((p > 0) & (p < 1))
    assert rec.records and not any(
        "persistent_cache" in x or "table_layouts" in x for x in rec.records
    )


def test_load_and_reload_spans_carry_the_pinned_leaves_and_bytes(pinned, tmp_path):
    _commit(tmp_path, 1)
    r = ServeRunner(_cfg(tmp_path))
    sink = _Sink()
    r.span_sink = sink
    r.load()
    _commit(tmp_path, 2)
    r.maybe_reload()
    assert [x["name"] for x in sink] == ["serve_load", "reload"]
    table = r.generation.tables["wv"]
    for span in sink:
        assert span["pinned_leaves"] == 1
        assert span["pinned_bytes"] == table.on_device_size_in_bytes() >= table.nbytes
        assert span["bytes"] == table.nbytes
