"""The chip's compiler, asked before any chip run: the main path's Pallas
kernels and step programs are lowered through Mosaic and compiled for a
DESCRIBED v5e (nothing is attached, nothing runs) at the real FM widths
— 2^22 slots, B=65536 x 32 occurrences, K=1+10, packed storage.

What interpret mode and the CPU suite cannot see shows here: a slice
not aligned to the tiling, a kernel over its VMEM budget, a program
that does not fit the device. A pass is a compile, never a run.

The topology is described inside a fixture (never at import: only one
process may load the TPU library, and every xdist worker imports every
test file), and all such tests live in THIS file, so one worker loads
the library once.
"""

import numpy as np
import pytest

LOG2_SLOTS, BATCH, NNZ, K, PACK = 22, 65536, 32, 11, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next one warns and
    compiles again): keep these compiles out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """The op wrappers ask jax.default_backend() and would take their
    XLA branch here; steer them from the test, not from an option."""
    from xflow_tpu.ops import sorted_table

    monkeypatch.setattr(sorted_table, "_on_tpu", lambda: True)


def _fm_cfg(log2_slots=LOG2_SLOTS):
    from xflow_tpu.config import Config, override

    return override(Config(), **{
        "model.name": "fm", "data.log2_slots": log2_slots,
        "data.batch_size": BATCH, "data.max_nnz": NNZ,
    })


def _slots_mask(log2_slots=LOG2_SLOTS):
    rng = np.random.default_rng(0)
    return (
        rng.integers(0, 1 << log2_slots, (BATCH, NNZ)).astype(np.int32),
        np.ones((BATCH, NNZ), np.float32),
    )


def _shapes(tree, sharding):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
    )


def _pallas_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _recorded_pallas_calls(compiled) -> int:
    """`pallas_calls` as the program's kind="compile" record holds it
    (telemetry.CompileRecorder): the count a run's metrics stream shows."""
    from xflow_tpu.telemetry import CompileRecorder

    return CompileRecorder()._op_scopes(compiled)[3]


def _phase_map(compiled, unnamed_ok: int = 0) -> dict:
    """{operation -> label} as the step's compile record would hold it
    (telemetry.op_phases), off the chip's own compiler's text: what the
    CPU suite's tests/test_phase_map.py reads off XLA's CPU backend."""
    from xflow_tpu.telemetry import op_phases

    phases = op_phases(compiled.as_text())
    unnamed = [op for op, label in phases.items() if not label]
    assert len(unnamed) < max(0.05 * len(phases), unnamed_ok + 1), unnamed
    return phases


def _kernels(phases: dict, text: str) -> list:
    """[(Mosaic call's name, numbered suffix folded; its label)], sorted."""
    import re

    names = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    return sorted((re.sub(r"\.\d+$", "", n), phases[n]) for n in names)


def _kernel_cases():
    """name -> (fn, arg shapes as (shape, dtype) tuples): the six kernels
    of the sorted engine at the FM main-path widths."""
    from xflow_tpu.config import FTRLConfig
    from xflow_tpu.ops import sorted_table as st

    S = 1 << LOG2_SLOTS
    n_win = S // st.WINDOW
    n_occ = st.padded_len(BATCH * NNZ)
    table = ((S // PACK, PACK * K), np.float32)
    slots = ((n_occ,), np.int32)
    win_off = ((n_win + 1,), np.int32)
    d_occ = ((st._k8(K), n_occ), np.float32)
    # the multi-buffer form: four source buffers of one capacity (the
    # four-chip fullshard stream, or NS=4 sub-batches on one device)
    nbuf = 4
    cap = (n_occ // nbuf // st.CHUNK + 1) * st.CHUNK
    m_slots = ((nbuf * cap,), np.int32)
    m_off = ((nbuf, n_win + 1), np.int32)
    m_d = ((st._k8(K), nbuf * cap), np.float32)
    hp = FTRLConfig()
    return {
        "gather": (
            lambda t, s, w: st._gather_pallas(t, s, w, False, PACK),
            (table, slots, win_off),
        ),
        "gather_multi": (
            lambda t, s, o: st._gather_pallas_multi(t, s, o, cap, False, PACK),
            (table, m_slots, m_off),
        ),
        "scatter": (
            lambda d, s, w: st._scatter_pallas(d, s, w, S, K, False, PACK),
            (d_occ, slots, win_off),
        ),
        "scatter_multi": (
            lambda d, s, o: st._scatter_pallas_multi(d, s, o, S, K, cap, False, PACK),
            (m_d, m_slots, m_off),
        ),
        "scatter_ftrl": (
            lambda d, s, w, a, b, c: st._scatter_ftrl_pallas(
                d, s, w, a, b, c, K, hp, False, PACK
            ),
            (d_occ, slots, win_off, table, table, table),
        ),
        "rowsum": (
            lambda v, r: st._rowsum_pallas(v, r, BATCH),
            (((24, n_occ), np.float32), slots),
        ),
    }


@pytest.mark.parametrize(
    "kernel",
    ["gather", "gather_multi", "scatter", "scatter_multi", "scatter_ftrl", "rowsum"],
)
def test_kernel_compiles_for_v5e(kernel, one_chip, no_persistent_cache):
    import jax

    fn, arg_shapes = _kernel_cases()[kernel]
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in arg_shapes
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert _pallas_calls(compiled) == 1


@pytest.mark.parametrize(
    "k,window",
    [(11, 2048), (96, 2048), (128, 1024), (129, 1024), (157, 1024), (160, 1024), (176, 512), (256, 256)],
)
def test_fused_kernel_fits_vmem_at_the_window_its_rule_gives(k, window, one_chip, no_persistent_cache):
    """`ops/sorted_table.state_window`: FM's 11-float row keeps 2048
    slots a grid step, and so do packed rows up to 96 floats; 128, 129
    and FFM at Criteo's 39 fields x k=4 (157 floats) get 1024, and the
    rule's choice compiles at each step of its ladder. At 2048 the
    157-float kernel was refused, "Scoped allocation with size 16.94M and
    limit 16.00M" (128 floats: 21.10M; 176 at 1024: 16.61M) — the step
    died in the compiler at its first batch."""
    import jax

    from xflow_tpu.config import FTRLConfig
    from xflow_tpu.ops import sorted_table as st

    assert st.state_window(k, PACK) == window
    S, n_occ = 1 << 16, st.padded_len(8192)
    table = jax.ShapeDtypeStruct((S // PACK, PACK * k), np.float32, sharding=one_chip)
    args = [
        jax.ShapeDtypeStruct((st._k8(k), n_occ), np.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((n_occ,), np.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((S // window + 1,), np.int32, sharding=one_chip),
        table, table, table,
    ]
    fn = lambda d, s, w, a, b, c: st._scatter_ftrl_pallas(d, s, w, a, b, c, k, FTRLConfig(), False, PACK)
    assert _pallas_calls(jax.jit(fn).lower(*args).compile()) == 1


def _ffm_cell_step(one_chip):
    """The single-device FFM step at `ffm-v4-f39-s21`'s sizes as the
    engine builds it: 39 fields x k=4 at 2^21 slots, B=65536, one
    feature a field, the aligned hybrid's flat plan with its placement
    permutation, the state pinned in the kernels' layout."""
    import jax

    from xflow_tpu.analysis.ir import _abstract_state, _capture, _CapturingRecorder
    from xflow_tpu.config import Config, override
    from xflow_tpu.data.schema import SparseBatch
    from xflow_tpu.models import get_model
    from xflow_tpu.optim import get_optimizer
    from xflow_tpu.train.engine import _sorted_arrays, state_formats
    from xflow_tpu.train.step import make_train_step

    nf = 39
    cfg = override(Config(), **{
        "model.name": "ffm", "model.num_fields": nf, "model.v_dim": 4,
        "data.log2_slots": 21, "data.batch_size": BATCH, "data.max_nnz": nf,
    })
    rng = np.random.default_rng(0)
    batch = SparseBatch(
        slots=rng.integers(0, cfg.num_slots, (BATCH, nf)).astype(np.int32),
        fields=np.tile(np.arange(nf, dtype=np.int32), (BATCH, 1)),
        mask=np.ones((BATCH, nf), np.float32),
        labels=np.zeros(BATCH, np.float32), row_mask=np.ones(BATCH, np.float32),
    )
    arrays = _sorted_arrays(cfg, lambda a, b: a)(batch)
    model, opt = get_model("ffm"), get_optimizer("ftrl")
    abstract = _abstract_state(model, opt, cfg)
    formats = state_formats("sorted", abstract, jax.tree.map(lambda _: one_chip, abstract))
    _, step = _capture(
        lambda: make_train_step(
            model, opt, cfg, recorder=_CapturingRecorder(), state_formats=formats
        )
    )
    return step, _shapes(abstract, one_chip), _shapes(arrays, one_chip), arrays


def test_ffm_cell_step_compiles_for_v5e(one_chip, no_persistent_cache, on_tpu):
    """Field-aware FM at Criteo's shape on the normal path, every
    option at its default: the fused step compiles for one v5e chip with
    two Mosaic calls — the windowed gather and the fused scatter+FTRL at
    a 1024-slot window; FFM's row side is XLA's (the placement and the
    pair term over a block transposition: no dot is left in the step),
    it has no row-sum kernel — and 4.06 GB of pinned state + 6.7 GB of
    temporaries inside the chip's 15.75 GB."""
    step, state, batch, arrays = _ffm_cell_step(one_chip)
    assert arrays["win_off"].shape == ((1 << 21) // 1024 + 1,) and "ffm_invperm" in arrays
    compiled = step.lower(state, batch).compile()
    assert _pallas_calls(compiled) == _recorded_pallas_calls(compiled) == 2
    assert " dot(" not in compiled.as_text()
    # the step's phases by the program's own word: the two kernels keep the
    # names their roofline readers match, the row side's two labels sit
    # between them, and no table scatter stands alone in the fused step
    phases = _phase_map(compiled)
    assert _kernels(phases, compiled.as_text()) == [
        ("gather", "gather"), ("scatter_optimizer", "scatter_optimizer")]
    assert {"ffm_place", "ffm_pair", "rows", "update"} <= set(phases.values())
    assert not {"scatter", "exchange", "health"} & set(phases.values())
    mem = compiled.memory_analysis()
    assert 4.0e9 < mem.argument_size_in_bytes < 4.2e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def _single_device_step(cfg, one_chip):
    """(jitted single-device train step, abstract state, abstract batch)
    as `xflow train --no-mesh` builds it: FM on its flat sorted plan, LR
    on the row-major arrays, the state in the layout its engine names
    (train/engine.py `state_formats`)."""
    import jax

    from xflow_tpu.analysis.ir import (
        _abstract_state, _capture, _CapturingRecorder, _rowmajor_batch,
    )
    from xflow_tpu.models import get_model
    from xflow_tpu.ops.sorted_table import plan_sorted_stacked
    from xflow_tpu.optim import get_optimizer
    from xflow_tpu.train.engine import state_formats
    from xflow_tpu.train.step import make_train_step

    model, opt = get_model(cfg.model.name), get_optimizer("ftrl")
    if cfg.model.name == "fm":
        slots, mask = _slots_mask(cfg.data.log2_slots)
        plan = plan_sorted_stacked(slots, mask, cfg.num_slots, wire=True)
        rows = np.zeros((BATCH,), np.float32)
        batch = {
            "labels": rows, "row_mask": rows, "sorted_slots": plan.sorted_slots,
            "sorted_row": plan.sorted_row, "sorted_mask": plan.sorted_mask,
            "win_off": plan.win_off,
        }
    else:
        batch = _rowmajor_batch(cfg)
    abstract = _abstract_state(model, opt, cfg)
    formats = state_formats(
        "sorted" if cfg.model.name == "fm" else "row_major",
        abstract, jax.tree.map(lambda _: one_chip, abstract),
    )
    _, step = _capture(
        lambda: make_train_step(
            model, opt, cfg, recorder=_CapturingRecorder(), state_formats=formats
        )
    )
    return step, _shapes(abstract, one_chip), _shapes(batch, one_chip)


def test_fm_train_step_compiles_for_v5e(one_chip, no_persistent_cache, on_tpu):
    """The whole single-device FM step `xflow train --no-mesh` runs:
    gather + row sum + fused scatter/FTRL as Mosaic calls, donated state,
    inside one chip's memory."""
    step, state, batch = _single_device_step(_fm_cfg(), one_chip)
    compiled = step.lower(state, batch).compile()
    assert _pallas_calls(compiled) == _recorded_pallas_calls(compiled) == 3
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    # the gather is the only kernel named `gather`, the fused kernel the
    # only `scatter_optimizer` (the benchmark's readers match those names);
    # the row sums are `rows`, named after the `jvp()` autodiff writes
    phases = _phase_map(compiled)
    assert _kernels(phases, compiled.as_text()) == [
        ("gather", "gather"), ("jvp__", "rows"), ("scatter_optimizer", "scatter_optimizer")]
    assert set(phases.values()) <= {"gather", "rows", "update", "scatter_optimizer", ""}


@pytest.mark.parametrize("model_name", ["fm", "lr"])
def test_guarded_step_keeps_no_second_state(model_name, one_chip, no_persistent_cache, on_tpu):
    """The non-finite guard decides before the write, so the pre-step
    w, n, z need not outlive the update and are not read again: compiled
    with `skip` (the default) the fused FM step and the LR two-pass step
    take no more temporary memory than with `off`, and touch no more
    bytes than one gradient-sized read on top. Selecting old against new
    kept 1.5 whole leaves more in FM at this size (3.2 GB) and touched 26
    more; in LR the compiler recomputed the FTRL sweep for the check
    instead of keeping its result, and touched four more."""
    from xflow_tpu.config import override

    log2 = 24  # at 2^22 the row-side temporaries hide a kept FM leaf
    temp, touched = {}, {}
    for guard in ("off", "skip"):
        cfg = override(_fm_cfg(), **{
            "model.name": model_name, "data.log2_slots": log2,
            "train.nonfinite_guard": guard,
        })
        step, state, batch = _single_device_step(cfg, one_chip)
        compiled = step.lower(state, batch).compile()
        temp[guard] = compiled.memory_analysis().temp_size_in_bytes
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        touched[guard] = cost["bytes accessed"]
    leaf = (1 << log2) * (K if model_name == "fm" else 1) * 4
    assert temp["skip"] <= temp["off"] + leaf // 8, temp
    assert touched["skip"] <= touched["off"] + 1.5 * leaf, touched


def test_fm_2_26_step_fits_one_chip_in_the_kernels_layout(one_chip, no_persistent_cache, on_tpu):
    """With the state pinned in the layout the kernels take, the
    single-device step at 2^26 slots compiles into one chip: 12.9 GB of
    padded w, n, z as arguments and 1.4 GB of temporaries. (Taking the
    state in the client's default layout it was refused, "Used 20.39G of
    15.75G hbm": three padded copies on top of the state.) It is still no
    one-chip cell: re-laying the third leaf on the way in needs 2 x 4.29 +
    2.95 + 4.29 = 15.8 GB, and the benchmark's set-up holds two states."""
    step, state, batch = _single_device_step(_fm_cfg(26), one_chip)
    mem = step.lower(state, batch).compile().memory_analysis()
    assert mem.argument_size_in_bytes > 12.8e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_state_is_born_sharded_at_2_27_on_four_chips(topo, no_persistent_cache):
    """The init program of `fm-v10-s27-x4` (train/state.py build_state:
    `init_state` under one jit with `out_shardings`): 17.7 GB of w, n, z
    that no chip holds come out a quarter a chip, the sampler's
    temporaries stay under one whole leaf, and the program fits a
    chip's 15.75 GB. Built eagerly on one device and sharded afterwards
    the same state died with RESOURCE_EXHAUSTED before the first step."""
    import jax

    from xflow_tpu.models import get_model
    from xflow_tpu.optim import get_optimizer
    from xflow_tpu.parallel.mesh import make_mesh, state_shardings
    from xflow_tpu.train.state import init_state

    cfg = _fm_cfg(27)
    model, opt = get_model("fm"), get_optimizer("ftrl")
    mesh = make_mesh(cfg, devices=topo.devices)

    def init():
        return init_state(model, opt, cfg)

    abstract = jax.eval_shape(init)
    compiled = jax.jit(init, out_shardings=state_shardings(abstract, mesh)).lower().compile()
    assert not any(
        c in compiled.as_text() for c in ("all-gather", "all-reduce", "all-to-all", "collective-permute")
    )
    mem = compiled.memory_analysis()
    whole = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(abstract))
    leaf = (1 << 27) * K * 4
    assert whole > 16e9 and mem.output_size_in_bytes <= whole // 4 + (1 << 20)
    assert mem.temp_size_in_bytes < leaf
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def _fullshard_step(log2_slots, topo):
    """The fullshard FM train step as `xflow train` builds it on the 2x2
    host, the state sharded over all four chips and in the layout the
    engine names (train/engine.py `state_formats`): its two programs,
    {"grad", "update": (jitted, abstract arguments)}, with the planned
    host "arrays" and the "abstract" state."""
    import jax

    from xflow_tpu.analysis.ir import _abstract_state, _with_shardings
    from xflow_tpu.models import get_model
    from xflow_tpu.ops.sorted_table import compact_plan_wire
    from xflow_tpu.optim import get_optimizer
    from xflow_tpu.parallel.mesh import (
        batch_sharding, make_mesh, replicated, state_shardings,
    )
    from xflow_tpu.parallel.sorted_fullshard import (
        make_fullshard_train_step, plan_fullshard_batch,
    )
    from xflow_tpu.train.engine import state_formats

    cfg = _fm_cfg(log2_slots)
    model, opt = get_model("fm"), get_optimizer("ftrl")
    mesh = make_mesh(cfg, devices=topo.devices)
    abstract = _abstract_state(model, opt, cfg)
    state = _with_shardings(abstract, state_shardings(abstract, mesh))
    slots, mask = _slots_mask(log2_slots)
    rows = np.zeros((BATCH,), np.float32)
    arrays = {"labels": rows, "row_mask": rows}
    arrays.update(plan_fullshard_batch(slots, mask, cfg, mesh))
    arrays = compact_plan_wire(
        arrays, rows_bound=BATCH // mesh.shape["data"], fields_bound=0
    )
    bsh = batch_sharding(mesh)
    batch = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=bsh[k])
        for k, v in arrays.items()
    }
    got = {}

    class Programs:
        """What the builder routes through `recorder.wrap`, by name."""

        def wrap(self, name, fn, **static_fields):
            got[name] = fn
            return lambda *args: None

    call = make_fullshard_train_step(
        opt, cfg, mesh, recorder=Programs(),
        state_formats=lambda s: state_formats("fullshard", s, state_shardings(s, mesh)),
    )
    with pytest.raises(TypeError):  # the stand-ins hand back nothing to unpack
        call(state, batch)
    grad, update = got["train_step.fullshard.fm"], got["update_step.fullshard.fm"]
    table = state.tables["wv"]
    scalar = jax.ShapeDtypeStruct((), np.float32, sharding=replicated(mesh))
    return {
        "grad": (grad, (table, batch)),
        "update": (update, (state, table, scalar, scalar)),
        "arrays": arrays, "abstract": abstract,
    }


_COMPILED = {}  # the 2^27 fullshard step takes a while: two tests read one compile


def _fullshard_compiled(log2_slots, topo):
    """{"grad", "update": the step's two programs compiled, "arrays", "abstract"}."""
    if log2_slots not in _COMPILED:
        built = _fullshard_step(log2_slots, topo)
        for name in ("grad", "update"):
            fn, args = built[name]
            built[name] = fn.lower(*args).compile()
        _COMPILED[log2_slots] = built
    return _COMPILED[log2_slots]


@pytest.mark.parametrize("log2_slots", [LOG2_SLOTS, 27])
def test_fm_fullshard_step_compiles_for_four_chips(log2_slots, topo, no_persistent_cache, on_tpu):
    """The mesh engine `xflow train` picks on more than one device: the
    fully-sharded FM step over the 2x2 host, each chip holding a quarter
    of the state — at 2^27 slots (`fm-v10-s27-x4`) a quarter of a state
    no chip holds whole, with its temporaries inside a chip's 15.75 GB.
    The gradient program holds the exchange, the on-device merge (a
    sort) and three kernels that each walk one span a table window; the
    update program, the only one that writes the state, holds none."""
    import jax

    built = _fullshard_compiled(log2_slots, topo)
    compiled, update, arrays, abstract = (built[k] for k in ("grad", "update", "arrays", "abstract"))
    text = compiled.as_text()
    assert _pallas_calls(update) == 0 and "all-to-all" not in update.as_text()
    assert _pallas_calls(compiled) == _recorded_pallas_calls(compiled) == 3
    assert "all-to-all" in text
    # three kernels, three phases: the windowed gather, its transpose (the
    # two-pass scatter keeps the gather's name in the trace, and reads
    # `scatter` through autodiff's `transpose(`) and the row sums; the
    # merge's sort and every collective are `exchange`; the gradient's
    # relayout on each side of the cut is booked to that side's phase
    import re

    phases = _phase_map(compiled)
    assert _kernels(phases, text) == [("gather", "gather"), ("gather", "scatter"), ("rows", "rows")]
    from xflow_tpu.telemetry import _operations

    by_opcode: dict = {}
    for name, (opcode, _, _) in _operations(text).items():
        by_opcode.setdefault(opcode, set()).add(phases.get(name))
    assert by_opcode["sort"] == by_opcode["all-to-all"] == by_opcode["all-gather"] == {"exchange"}
    leaf = f"f32[{(1 << log2_slots) // 4 // PACK},{PACK * K}]"
    relaid = lambda c: re.findall(r"%([\w.\-]+) = " + re.escape(leaf) + r"\S* copy\(", c.as_text())
    assert [phases[n] for n in relaid(compiled)] == ["scatter"]
    # (the loss and the row count pass through the update program: two
    # scalar copies from a parameter to the result, in no phase)
    updating = _phase_map(update, unnamed_ok=2)
    assert [updating[n] for n in relaid(update)] == ["update"]
    assert set(updating.values()) <= {"update", "exchange", ""}
    # the four received buffers are merged into one slot-sorted stream on
    # the device (one sort), and the kernels walk ONE span a window: no
    # Mosaic call takes the buffers' [D, wpo+1] offset table
    assert " sort(" in text
    from xflow_tpu.ops.sorted_table import WINDOW

    d, wpo1 = arrays["fs_off"].shape[-2:]
    assert (d, wpo1) == (4, (1 << log2_slots) // 4 // WINDOW + 1)
    kernels = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    assert not any(f"s32[{d},{wpo1}]" in ln for ln in kernels), kernels
    assert sum(f"s32[{wpo1}]" in ln for ln in kernels) == 2  # gather, transpose
    whole = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(abstract)
    )
    for program in (compiled, update):
        mem = program.memory_analysis()
        # a quarter a chip, its 88 columns padded to the 128 lanes of a
        # tile, and in the update the shard's gradient beside it
        assert mem.argument_size_in_bytes < 0.25 * whole * (128 / 88 + 1 / 3) + (1 << 26)
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def _state_leaf_formats(formats):
    """The table and optimizer leaves of a compiled step's state formats."""
    import jax

    return jax.tree.leaves((formats.tables, formats.opt_state))


@pytest.mark.parametrize("program", ["fm_2_25_one_chip", "fm_2_27_fullshard", "lr_2_29_one_chip"])
def test_state_lives_in_the_layout_its_kernels_take(program, topo, one_chip, no_persistent_cache, on_tpu):
    """The benchmark's three step programs, built and formatted as the
    engine does it. FM (one chip and a chip of four): the program that
    writes the state takes w, n, z row-major in (8, 128) tiles — what
    the Pallas calls take — and hands them back the same, so none of the
    six table-sized `copy` of a leaf is left, the padded leaves are the
    donated state itself and not 6.44 / 8.59 GB of temporaries beside
    it, and the whole state is aliased. On four chips the step is two
    programs (parallel/sorted_fullshard.py `programs`): the gradient
    crosses between them in the default layout, one `copy` on each side.
    LR (1-D leaves, the row-major engine): nothing is pinned."""
    import jax

    from xflow_tpu.train.engine import KERNEL_LAYOUT, state_formats

    leaf = f"f32[{(1 << 25) // PACK},{PACK * K}]"  # a chip's share at 2^27 too
    copies = lambda c: [ln for ln in c.as_text().splitlines() if " copy(" in ln and leaf in ln]
    if program == "lr_2_29_one_chip":
        from xflow_tpu.config import override

        cfg = override(_fm_cfg(29), **{"model.name": "lr"})
        step, state, batch = _single_device_step(cfg, one_chip)
        # by the leaf's rank, whatever engine asks
        shardings = jax.tree.map(lambda _: one_chip, state)
        assert state_formats("row_major", state, shardings) is None
        assert state_formats("sorted", state, shardings) is None
        lowered = step.lower(state, batch)
        assert "T(8,128)" not in lowered.as_text()
        (st_in, _), _ = lowered.compile().input_formats
        assert all(f.layout.major_to_minor == (0,) for f in _state_leaf_formats(st_in))
        return
    if program == "fm_2_25_one_chip":
        step, state, batch = _single_device_step(_fm_cfg(25), one_chip)
        writer, temp_limit = step.lower(state, batch).compile(), 2e9
        assert not copies(writer)
    else:
        built = _fullshard_compiled(27, topo)
        writer, temp_limit = built["update"], 3e9
        # the table comes in pinned and is not copied; the gradient goes
        # out in the default layout (a program read back from the
        # persistent cache may hand back no other) and comes in again
        (table_in, _), _ = built["grad"].input_formats
        assert table_in.layout == KERNEL_LAYOUT
        assert built["grad"].output_formats[1].layout != KERNEL_LAYOUT
        assert [("gather" in ln, "param" in ln) for ln in copies(built["grad"])] == [(True, False)]
        assert len(copies(writer)) == 1 and 'op_name="grads"' in copies(writer)[0]
    (st_in, *_), _ = writer.input_formats
    st_out = writer.output_formats[0]
    ins, outs = _state_leaf_formats(st_in), _state_leaf_formats(st_out)
    assert len(ins) == 3 and [f.layout for f in ins] == [KERNEL_LAYOUT] * 3
    assert [f.layout for f in outs] == [f.layout for f in ins]
    mem = writer.memory_analysis()
    assert mem.temp_size_in_bytes < temp_limit
    padded = (1 << 25) // PACK * 128 * 4  # 88 columns in 128 lanes
    # w, n, z whole, and the step counter's word
    assert 3 * padded < mem.alias_size_in_bytes <= 3 * padded + 4096


def test_occupancy_sweep_compiles_in_seconds(one_chip, no_persistent_cache):
    """The end-of-fit occupancy sweep over the packed FTRL accumulator at
    2^24 slots. Written as a reshape to [S/8, 8, 11] this one program
    took the chip's compiler 225 s (and 156 s here); grouped by a matmul
    it takes about one."""
    import time

    import jax
    import jax.numpy as jnp

    from xflow_tpu.train.trainer import _slot_any

    n = jax.ShapeDtypeStruct(((1 << 24) // PACK, PACK * K), jnp.float32, sharding=one_chip)
    t0 = time.perf_counter()
    jax.jit(lambda n: jnp.mean(_slot_any(n > 0, K))).lower(n).compile()
    assert time.perf_counter() - t0 < 60
