"""Sorted-window table engine (ops/sorted_table.py): plan correctness,
gather/scatter parity vs direct XLA ops, custom-VJP gradients, and FM
forward/step equality between the sorted and row-major paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.models import get_model
from xflow_tpu.ops.sorted_table import (
    CHUNK,
    WINDOW,
    _gather_pallas,
    _gather_xla,
    _k8,
    _scatter_pallas,
    _scatter_xla,
    plan_sorted_batch,
    table_gather_sorted,
)

S = 2 * WINDOW
K = 11
K8 = _k8(K)


def _random_case(rng, B=16, F=8, mask_p=0.7):
    slots = rng.integers(0, S, (B, F)).astype(np.int32)
    mask = (rng.random((B, F)) < mask_p).astype(np.float32)
    table = rng.normal(size=(S, K)).astype(np.float32)
    return slots, mask, table


def test_plan_invariants():
    rng = np.random.default_rng(0)
    slots, mask, _ = _random_case(rng)
    plan = plan_sorted_batch(slots, mask, S)
    n = slots.size
    assert plan.sorted_slots.shape[0] % CHUNK == 0
    assert plan.sorted_slots.shape[0] >= n + CHUNK
    assert np.all(np.diff(plan.sorted_slots) >= 0)  # sorted incl. pads
    assert np.all(plan.sorted_slots[n:] == S - 1)  # pad = last slot, mask 0
    assert np.all(plan.sorted_mask[n:] == 0.0)
    assert plan.win_off.shape == (S // WINDOW + 1,)
    # every position (pads included) is owned by some window
    assert plan.win_off[0] == 0 and plan.win_off[-1] == plan.sorted_slots.shape[0]
    # every occurrence is within its window's range
    for t in range(S // WINDOW):
        seg = plan.sorted_slots[plan.win_off[t] : plan.win_off[t + 1]]
        assert np.all((seg >= t * WINDOW) & (seg < (t + 1) * WINDOW))
    # permutation round-trip: multiset of (slot, mask) pairs preserved
    got = sorted(zip(plan.sorted_slots[:n].tolist(), plan.sorted_mask[:n].tolist()))
    want = sorted(zip(slots.ravel().tolist(), mask.ravel().tolist()))
    assert got == want


def test_gather_sorted_matches_direct():
    rng = np.random.default_rng(1)
    slots, mask, table = _random_case(rng)
    plan = plan_sorted_batch(slots, mask, S)
    occ_t = table_gather_sorted(
        jnp.asarray(table), jnp.asarray(plan.sorted_slots), jnp.asarray(plan.win_off)
    )
    n = slots.size
    assert occ_t.shape == (K8, plan.sorted_slots.shape[0])
    np.testing.assert_allclose(
        np.asarray(occ_t[:K, :n]).T, table[plan.sorted_slots[:n]], rtol=1e-6
    )
    # pad cols hold row S-1's values (owned by the last window, never
    # uninitialized memory); consumers mask them out via sorted_mask
    np.testing.assert_allclose(
        np.asarray(occ_t[:K, n:]).T,
        np.broadcast_to(table[S - 1], (occ_t.shape[1] - n, K)),
        rtol=1e-6,
    )
    np.testing.assert_array_equal(np.asarray(occ_t[K:]), 0.0)  # pad rows


def test_scatter_vjp_matches_xla_scatter():
    rng = np.random.default_rng(2)
    slots, mask, table = _random_case(rng, B=32, F=16)
    plan = plan_sorted_batch(slots, mask, S)
    n = slots.size
    np_len = plan.sorted_slots.shape[0]
    d_t = rng.normal(size=(K8, np_len)).astype(np.float32)
    d_t[K:] = 0.0
    d_t[:, n:] = 0.0

    def f(tab):
        occ_t = table_gather_sorted(
            tab, jnp.asarray(plan.sorted_slots), jnp.asarray(plan.win_off)
        )
        return (occ_t * jnp.asarray(d_t)).sum()

    d_table = jax.grad(f)(jnp.asarray(table))
    want = np.zeros((S, K), np.float32)
    np.add.at(want, plan.sorted_slots[:n], d_t[:K, :n].T)
    np.testing.assert_allclose(np.asarray(d_table), want, rtol=1e-5, atol=1e-6)


def _plan_stream(seed, n_win, window):
    # a batch through the planner, as the step gets it (seeds 3 and 4:
    # the two windows and ~1.3 chunks the test has always had)
    rng = np.random.default_rng(seed)
    S = n_win * window
    slots = rng.integers(0, S, (24, 11)).astype(np.int32)
    mask = (rng.random((24, 11)) < 0.7).astype(np.float32)
    plan = plan_sorted_batch(slots, mask, S, window=window)
    return plan.sorted_slots, plan.win_off


def _counted_stream(counts, tail=0, head=0):
    """A slot-sorted stream with counts[t] occurrences in window t, its
    offsets, and pads that repeat the last slot (so windows past it are
    empty; the planner's pads, slot S-1, fill the last window instead).
    `tail` positions at the end are owned by no window and `head`
    positions at the start: a stream that begins or ends inside a
    chunk."""
    def make(seed, n_win, window):
        assert len(counts) == n_win
        rng = np.random.default_rng(seed)
        real = np.concatenate([
            np.sort(rng.integers(t * window, (t + 1) * window, c))
            for t, c in enumerate(counts)
        ]).astype(np.int32)
        n_pos = (real.size // CHUNK + 2) * CHUNK
        ss = np.concatenate([real, np.full(n_pos - real.size, real[-1], np.int32)])
        off = np.searchsorted(ss, np.arange(0, (n_win + 1) * window, window))
        off = np.clip(off, head, n_pos - tail).astype(np.int32)
        return ss, off

    return make


def _wrapped_stream(seed, n_win, window):
    # D = 2 buffers over ONE table, concatenated: the grid has 2 * n_tw
    # steps and step t owns table window t % n_tw; the offsets stay
    # monotone over the whole grid
    a, off_a = _counted_stream([100] * n_win)(seed, n_win, window)
    b, off_b = _counted_stream([0, 0] + [150] * (n_win - 2))(seed + 1, n_win, window)
    return np.concatenate([a, b]), np.concatenate([off_a[:-1], off_a[-1] + off_b])


# name -> (windows of the table, stream maker, what the stream is).
# The carried chunk chain (ops/sorted_table.py `_gather_span`) must
# survive each: the scratch, the SMEM carry and the DMA semaphores live
# from one grid step to the next
_CHAIN_STREAMS = {
    "seed3": (2, lambda s, n, w: _plan_stream(3, n, w)),
    "seed4": (2, lambda s, n, w: _plan_stream(4, n, w)),
    # the benchmark cells' regime: a window is a quarter of a chunk
    "quarter_chunk": (16, _counted_stream([128] * 16)),
    # empty windows at the start, in the middle and at the end
    "empty_windows": (
        16, _counted_stream([0, 0, 100, 130, 0, 0, 0, 90, 700, 0, 128, 128, 5, 0, 0, 0])
    ),
    "span_of_chunks": (16, _counted_stream([30, 1500, 0, 40] + [100] * 12)),
    # 1024 occurrences: the last real one ends a chunk, two pad chunks follow
    "chunk_edge": (16, _counted_stream([64] * 16)),
    # the line starts and ends inside a chunk (no planner makes this):
    # the close writes a partial chunk
    "inside_chunk": (16, _counted_stream([128] * 16, tail=CHUNK + 200, head=70)),
    "grid_wraps": (16, _wrapped_stream),
}


# the scatters take one buffer over the table with every position owned
_GATHER_ONLY = ("inside_chunk", "grid_wraps")


@pytest.mark.parametrize("pack", [1, 8])
@pytest.mark.parametrize(
    "kernel,stream",
    [
        (kernel, stream)
        for kernel in ("gather", "scatter", "scatter_ftrl")
        for stream in _CHAIN_STREAMS
        if kernel == "gather" or stream not in _GATHER_ONLY
    ],
)
def test_pallas_interpret_matches_xla(kernel, stream, pack):
    """The single-stream TPU kernels, run in interpreter mode, equal the
    XLA path on every shape of stream their carried chunk chain meets.
    The fused scatter+FTRL is held to `_scatter_xla` followed by
    `optim/ftrl._update_one`, the composition it replaces."""
    pltpu = pytest.importorskip("jax.experimental.pallas.tpu")

    from xflow_tpu.config import FTRLConfig
    from xflow_tpu.ops.sorted_table import (
        _scatter_ftrl_pallas,
        pack_table,
        state_window,
    )
    from xflow_tpu.optim.ftrl import _update_one

    if not hasattr(pltpu, "force_tpu_interpret_mode"):
        pytest.skip("pallas TPU interpret mode unavailable in this jax build")
    n_tw, make = _CHAIN_STREAMS[stream]
    window = state_window(K, pack)
    size = n_tw * window
    ss, off = make(11, n_tw, window)
    owned = slice(int(off[0]), int(off[-1]))
    rng = np.random.default_rng(5)
    table = rng.normal(size=(size, K)).astype(np.float32)
    jt = jnp.asarray(pack_table(table) if pack > 1 else table)
    jss, joff = jnp.asarray(ss), jnp.asarray(off)
    # rtol 5e-5, not exact: the kernels' 3-term bf16 decomposition
    # (_dot_f32) reconstructs f32 bit-exactly on the real MXU
    # (verified on-device against the XLA gather), but the INTERPRETER's
    # bf16 rounding emulation can drop the low term's last ulp on rare
    # elements (~2^-16 relative). This test gates the structural parity
    # (windows, chain, offsets), not MXU arithmetic.
    if kernel == "gather":
        with pltpu.force_tpu_interpret_mode():
            occ_p = np.asarray(_gather_pallas(jt, jss, joff, False, pack))
        occ_x = np.asarray(_gather_xla(jt, jss, joff, pack))
        np.testing.assert_allclose(occ_p[:K, owned], occ_x[:K, owned], rtol=5e-5)
        np.testing.assert_array_equal(occ_p[K:, owned], 0.0)
        # a column of a visited chunk that no window owns holds its
        # slot's row where that slot's window visited the chunk, else
        # zeros — never what the buffer held before (chunks past the
        # stream's end are not written at all)
        first, last = owned.start // CHUNK * CHUNK, -(-owned.stop // CHUNK) * CHUNK
        for cols in (slice(first, owned.start), slice(owned.stop, last)):
            got, row = occ_p[:K, cols], occ_x[:K, cols]
            assert np.all((got == 0.0).all(axis=0) | np.isclose(got, row, rtol=5e-5).all(axis=0))
        return
    d_t = jnp.asarray(rng.normal(size=(K8, ss.size)).astype(np.float32))
    g_x = _scatter_xla(d_t, jss, joff, size, K, pack)
    if kernel == "scatter":
        with pltpu.force_tpu_interpret_mode():
            g_p = _scatter_pallas(d_t, jss, joff, size, K, False, pack)
        # same interpreter-emulation tolerance as the gather above
        np.testing.assert_allclose(np.asarray(g_p), np.asarray(g_x), rtol=5e-5, atol=2e-5)
        return
    hp = FTRLConfig()
    n0 = jnp.abs(jnp.asarray(rng.normal(size=jt.shape).astype(np.float32)))
    z0 = jnp.asarray(rng.normal(size=jt.shape).astype(np.float32))
    with pltpu.force_tpu_interpret_mode():
        got = _scatter_ftrl_pallas(d_t, jss, joff, jt, n0, z0, K, hp, False, pack)
    want = _update_one(jt, n0, z0, g_x, hp.alpha, hp.beta, hp.lambda1, hp.lambda2)
    for leaf, g, w in zip("wnz", got, want):
        # the gradient's emulation noise, through FTRL's square and divide
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-5, err_msg=leaf
        )


def test_rowsum_pallas_interpret_matches_xla():
    # the TPU row-sum kernel (scalar-core RMW into a VMEM-resident
    # accumulator), run in interpreter mode, must equal segment_sum
    pltpu = pytest.importorskip("jax.experimental.pallas.tpu")

    from xflow_tpu.ops.sorted_table import _rowsum_pallas

    if not hasattr(pltpu, "force_tpu_interpret_mode"):
        pytest.skip("pallas TPU interpret mode unavailable in this jax build")
    rng = np.random.default_rng(17)
    n, ch, rows_n = CHUNK, 24, 40
    rows = jnp.asarray(rng.integers(0, rows_n, n).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(ch, n)).astype(np.float32))
    with pltpu.force_tpu_interpret_mode():
        got = _rowsum_pallas(vals, rows, rows_n)
    want = jax.ops.segment_sum(vals.T, rows, num_segments=rows_n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_rowsum_grad_matches_segment_sum():
    from xflow_tpu.ops.sorted_table import row_sums_sorted

    rng = np.random.default_rng(18)
    n, ch, rows_n = CHUNK, 8, 12
    rows = jnp.asarray(rng.integers(0, rows_n, n).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(ch, n)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(rows_n, ch)).astype(np.float32))

    def f_custom(v):
        return (row_sums_sorted(v, rows, rows_n) * w).sum()

    def f_ref(v):
        return (jax.ops.segment_sum(v.T, rows, num_segments=rows_n) * w).sum()

    np.testing.assert_allclose(
        np.asarray(jax.grad(f_custom)(vals)), np.asarray(jax.grad(f_ref)(vals)),
        rtol=1e-5, atol=1e-6,
    )


def test_native_plan_matches_numpy(monkeypatch):
    """xf_plan_sorted (C radix sort) is bit-identical to the numpy
    argsort planner — both stable, same pads, same win_off."""
    pytest.importorskip("ctypes")
    try:
        from xflow_tpu.data.native import native_plan_sorted  # noqa: F401 — builds lib
        from xflow_tpu.data.native import get_lib

        get_lib()
    except Exception:
        pytest.skip("native toolchain unavailable")
    import xflow_tpu.ops.sorted_table as st

    rng = np.random.default_rng(21)
    for B, F, with_fields in [(16, 8, False), (64, 8, True), (1, 1, False), (7, 3, True)]:
        slots = rng.integers(0, S, (B, F)).astype(np.int32)
        mask = (rng.random((B, F)) < 0.7).astype(np.float32)
        fields = rng.integers(0, 6, (B, F)).astype(np.int32) if with_fields else None

        monkeypatch.setattr(st, "_NATIVE_PLAN", None)
        monkeypatch.setenv("XFLOW_NO_NATIVE_PLAN", "1")
        py = st.plan_sorted_batch(slots, mask, S, fields=fields)
        monkeypatch.delenv("XFLOW_NO_NATIVE_PLAN")
        monkeypatch.setattr(st, "_NATIVE_PLAN", None)
        nat = st.plan_sorted_batch(slots, mask, S, fields=fields)
        assert st._NATIVE_PLAN, "native planner did not engage"

        np.testing.assert_array_equal(nat.sorted_slots, py.sorted_slots)
        np.testing.assert_array_equal(nat.sorted_row, py.sorted_row)
        np.testing.assert_array_equal(nat.sorted_mask, py.sorted_mask)
        np.testing.assert_array_equal(nat.win_off, py.win_off)
        if with_fields:
            np.testing.assert_array_equal(nat.sorted_fields, py.sorted_fields)
        else:
            assert nat.sorted_fields is None and py.sorted_fields is None
    monkeypatch.setattr(st, "_NATIVE_PLAN", None)


@pytest.mark.parametrize("model_name, table", [("fm", "wv"), ("mvm", "v")])
def test_trainer_sorted_layout_matches_off(tmp_path, model_name, table):
    # end-to-end: identical final tables and AUC with the layout on vs off
    from xflow_tpu.data.synth import generate_shards
    from xflow_tpu.train.trainer import Trainer

    generate_shards(str(tmp_path / "train"), 1, 400, num_fields=5, ids_per_field=60, seed=7)

    def run(sorted_layout):
        cfg = override(
            Config(),
            **{
                "data.train_path": str(tmp_path / "train"),
                "data.test_path": str(tmp_path / "train"),
                "data.log2_slots": 12,
                "data.batch_size": 50,
                "data.max_nnz": 8,
                "data.sorted_layout": sorted_layout,
                "model.name": model_name,
                "model.num_fields": 5,
                "train.epochs": 2,
                "train.pred_dump": False,
            },
        )
        t = Trainer(cfg)
        assert (t.engine == "sorted") == (sorted_layout == "on")
        t.fit()
        return t

    t_on, t_off = run("on"), run("off")
    np.testing.assert_allclose(
        np.asarray(t_on.state.tables[table]), np.asarray(t_off.state.tables[table]),
        rtol=1e-4, atol=1e-6,
    )
    auc_on, _ = t_on.evaluate()
    auc_off, _ = t_off.evaluate()
    assert auc_on == pytest.approx(auc_off, abs=1e-6)


def test_chunk_chain_counts():
    """`chunk_visits` / `chunk_loads` from the offsets alone: a window
    visits every chunk its span overlaps, an empty window none; a flat
    plan's chunks are loaded once each, a stacked plan's spans load what
    they visit."""
    from xflow_tpu.ops.sorted_table import chunk_chain_counts, plan_sorted_stacked

    C = CHUNK
    # four windows a chunk, then an empty one, then a span of three chunks
    off = np.array([0, 128, 256, 384, 512, 512, 512 + 2 * C + 1, 4 * C])
    assert chunk_chain_counts(off) == {"chunk_visits": 4 + 0 + 3 + 1, "chunk_loads": 4}
    assert chunk_chain_counts(np.zeros(5, np.int32)) == {"chunk_visits": 0, "chunk_loads": 0}
    # a line that starts and ends inside a chunk
    assert chunk_chain_counts(np.array([70, 600, 700])) == {"chunk_visits": 3, "chunk_loads": 2}
    # the benchmark's regime through the planner: every window a piece of a chunk
    rng = np.random.default_rng(0)
    S = 64 * WINDOW
    slots = rng.integers(0, S, (512, 16)).astype(np.int32)
    mask = np.ones(slots.shape, np.float32)
    plan = plan_sorted_batch(slots, mask, S)
    got = chunk_chain_counts(plan.win_off)
    assert got["chunk_loads"] == plan.sorted_slots.size // C  # the two pad chunks too
    assert 64 <= got["chunk_visits"] <= 64 + got["chunk_loads"]
    stacked = plan_sorted_stacked(slots, mask, S, num_sub=4)
    both = chunk_chain_counts(stacked.win_off)
    assert both["chunk_loads"] == both["chunk_visits"] == sum(
        chunk_chain_counts(o)["chunk_visits"] for o in stacked.win_off
    )


def test_fit_books_the_kernels_chunk_counts(tmp_path):
    """An armed sorted-engine run: every step record's `host` holds its
    batch's `chunk_visits` / `chunk_loads`, and the final record this
    fit()'s sums (the same on `TrainResult`); a row-major run has
    neither."""
    import json

    from xflow_tpu.data.synth import generate_shards
    from xflow_tpu.ops.sorted_table import chunk_chain_counts
    from xflow_tpu.train.trainer import Trainer

    generate_shards(str(tmp_path / "train"), 1, 256, 8, 50, seed=1)
    base = {
        "model.name": "fm", "data.log2_slots": 14, "data.batch_size": 64,
        "data.train_path": str(tmp_path / "train"), "data.max_nnz": 8,
        "model.num_fields": 8, "train.epochs": 1, "train.pred_dump": False,
        "train.log_every": 1,
    }

    def run(layout):
        path = tmp_path / f"m_{layout}.jsonl"
        trainer = Trainer(override(Config(), **{
            **base, "data.sorted_layout": layout, "train.metrics_path": str(path),
        }))
        res = trainer.fit()
        recs = [json.loads(line) for line in open(path)]
        return trainer, res, recs

    trainer, res, recs = run("on")
    assert trainer.engine == "sorted" and res.steps == 4
    hosts = [r["host"] for r in recs if "host" in r and "kind" not in r]
    # a batch of 64 x 8 occurrences: one chunk of data and the plan's two
    # of pads, booked with the batch (a window can hold two, or none)
    assert all(h.get("chunk_loads", 0) == 3 * h["batches"] for h in hosts)
    assert all(h["chunk_visits"] >= h["chunk_loads"] for h in hosts if h["batches"])
    final = [r for r in recs if r.get("final")][-1]
    assert final["chunk_loads"] == res.chunk_loads == 4 * 3
    assert final["chunk_visits"] == res.chunk_visits == sum(h.get("chunk_visits", 0) for h in hosts)
    trainer, res, recs = run("off")
    assert trainer.engine == "row_major" and res.chunk_visits == 0
    assert not any("chunk_visits" in r.get("host", {}) or "chunk_visits" in r for r in recs)


def test_mvm_sorted_forward_and_step_match_rowmajor():
    from xflow_tpu.optim import get_optimizer
    from xflow_tpu.train.state import TrainState
    from xflow_tpu.train.step import make_train_step

    cfg = override(Config(), **{"data.log2_slots": 12, "model.name": "mvm",
                                "model.v_dim": 3, "model.num_fields": 4,
                                "data.max_nnz": 6})
    assert cfg.num_slots == S
    model = get_model("mvm")
    rng = np.random.default_rng(9)
    B, F = 32, 6
    slots = rng.integers(0, S, (B, F)).astype(np.int32)
    fields = rng.integers(0, 4, (B, F)).astype(np.int32)
    mask = (rng.random((B, F)) < 0.8).astype(np.float32)
    v = (rng.normal(size=(S, 3)) * 0.1).astype(np.float32)
    labels = (rng.random(B) < 0.5).astype(np.float32)
    base = {
        "slots": jnp.asarray(slots),
        "fields": jnp.asarray(fields),
        "mask": jnp.asarray(mask),
        "labels": jnp.asarray(labels),
        "row_mask": jnp.ones((B,), jnp.float32),
    }
    plan = plan_sorted_batch(slots, mask, S, fields=fields)
    assert plan.sorted_fields is not None
    n = slots.size
    # fields ride the same permutation: multiset of (slot, field, mask)
    got = sorted(zip(plan.sorted_slots[:n].tolist(), plan.sorted_fields[:n].tolist(),
                     plan.sorted_mask[:n].tolist()))
    want = sorted(zip(slots.ravel().tolist(), fields.ravel().tolist(),
                      mask.ravel().tolist()))
    assert got == want
    srt = {
        **base,
        "sorted_slots": jnp.asarray(plan.sorted_slots),
        "sorted_row": jnp.asarray(plan.sorted_row),
        "sorted_mask": jnp.asarray(plan.sorted_mask),
        "sorted_fields": jnp.asarray(plan.sorted_fields),
        "win_off": jnp.asarray(plan.win_off),
    }
    out_r = model.forward({"v": jnp.asarray(v)}, base, cfg)
    out_s = model.forward({"v": jnp.asarray(v)}, srt, cfg)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_r), rtol=1e-4, atol=1e-6)

    opt = get_optimizer("ftrl")
    step = make_train_step(model, opt, cfg)
    t0 = {"v": jnp.asarray(v)}
    s_r, m_r = step(TrainState(t0, opt.init_state(t0), jnp.zeros((), jnp.int32)), base)
    t1 = {"v": jnp.asarray(v)}
    s_s, m_s = step(TrainState(t1, opt.init_state(t1), jnp.zeros((), jnp.int32)), srt)
    assert float(m_r["loss"]) == pytest.approx(float(m_s["loss"]), rel=1e-5)
    np.testing.assert_allclose(
        np.asarray(s_s.tables["v"]), np.asarray(s_r.tables["v"]), rtol=1e-4, atol=1e-6
    )


@pytest.mark.parametrize("model_name", ["fm", "mvm"])
def test_stacked_sub_batches_match_single_plan(model_name):
    """NS>1 (cache-resident sub-batching) is numerically identical to
    NS=1: same logits, same one-step table update."""
    from xflow_tpu.ops.sorted_table import plan_sorted_stacked
    from xflow_tpu.optim import get_optimizer
    from xflow_tpu.train.state import TrainState
    from xflow_tpu.train.step import make_train_step

    cfg = override(Config(), **{"data.log2_slots": 12, "model.name": model_name,
                                "model.v_dim": 3, "model.num_fields": 4,
                                "data.max_nnz": 6})
    model = get_model(model_name)
    rng = np.random.default_rng(13)
    B, F = 32, 6
    slots = rng.integers(0, S, (B, F)).astype(np.int32)
    fields = rng.integers(0, 4, (B, F)).astype(np.int32)
    mask = (rng.random((B, F)) < 0.8).astype(np.float32)
    tdim = 4 if model_name == "fm" else 3
    tname = "wv" if model_name == "fm" else "v"
    tab = (rng.normal(size=(S, tdim)) * 0.1).astype(np.float32)
    base = {
        "slots": jnp.asarray(slots), "fields": jnp.asarray(fields),
        "mask": jnp.asarray(mask),
        "labels": jnp.asarray((rng.random(B) < 0.5).astype(np.float32)),
        "row_mask": jnp.ones((B,), jnp.float32),
    }
    use_fields = fields if model_name == "mvm" else None

    def arrays(ns):
        p = plan_sorted_stacked(slots, mask, S, fields=use_fields, num_sub=ns)
        out = {**base, "sorted_slots": jnp.asarray(p.sorted_slots),
               "sorted_row": jnp.asarray(p.sorted_row),
               "sorted_mask": jnp.asarray(p.sorted_mask),
               "win_off": jnp.asarray(p.win_off)}
        if use_fields is not None:
            out["sorted_fields"] = jnp.asarray(p.sorted_fields)
        return out

    a1, a4 = arrays(1), arrays(4)
    assert a4["sorted_slots"].ndim == 2 and a4["sorted_slots"].shape[0] == 4
    out1 = model.forward({tname: jnp.asarray(tab)}, a1, cfg)
    out4 = model.forward({tname: jnp.asarray(tab)}, a4, cfg)
    np.testing.assert_allclose(np.asarray(out4), np.asarray(out1), rtol=1e-5, atol=1e-7)

    opt = get_optimizer("ftrl")
    step = make_train_step(model, opt, cfg)
    s1, _ = step(TrainState({tname: jnp.asarray(tab)},
                            opt.init_state({tname: jnp.asarray(tab)}),
                            jnp.zeros((), jnp.int32)), a1)
    s4, _ = step(TrainState({tname: jnp.asarray(tab)},
                            opt.init_state({tname: jnp.asarray(tab)}),
                            jnp.zeros((), jnp.int32)), a4)
    np.testing.assert_allclose(
        np.asarray(s4.tables[tname]), np.asarray(s1.tables[tname]),
        rtol=1e-4, atol=1e-6,
    )


@pytest.mark.parametrize("standard", [True, False])
def test_fm_sorted_forward_and_step_match_rowmajor(standard):
    from xflow_tpu.optim import get_optimizer
    from xflow_tpu.train.state import TrainState
    from xflow_tpu.train.step import make_train_step

    cfg = override(Config(), **{"data.log2_slots": 12, "model.v_dim": 3,
                                "model.num_fields": 4, "data.max_nnz": 6,
                                "model.fm_standard": standard})
    assert cfg.num_slots == S
    model = get_model("fm")
    rng = np.random.default_rng(5)
    B, F = 32, 6
    slots = rng.integers(0, S, (B, F)).astype(np.int32)
    mask = (rng.random((B, F)) < 0.8).astype(np.float32)
    wv = (rng.normal(size=(S, 4)) * 0.1).astype(np.float32)
    labels = (rng.random(B) < 0.5).astype(np.float32)
    base = {
        "slots": jnp.asarray(slots),
        "fields": jnp.asarray(rng.integers(0, 4, (B, F)), jnp.int32),
        "mask": jnp.asarray(mask),
        "labels": jnp.asarray(labels),
        "row_mask": jnp.ones((B,), jnp.float32),
    }
    plan = plan_sorted_batch(slots, mask, S)
    srt = {
        **base,
        "sorted_slots": jnp.asarray(plan.sorted_slots),
        "sorted_row": jnp.asarray(plan.sorted_row),
        "sorted_mask": jnp.asarray(plan.sorted_mask),
        "win_off": jnp.asarray(plan.win_off),
    }
    out_r = model.forward({"wv": jnp.asarray(wv)}, base, cfg)
    out_s = model.forward({"wv": jnp.asarray(wv)}, srt, cfg)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_r), rtol=1e-4, atol=1e-6)

    opt = get_optimizer("ftrl")
    t0 = {"wv": jnp.asarray(wv)}
    step = make_train_step(model, opt, cfg)
    s_r, m_r = step(TrainState(t0, opt.init_state(t0), jnp.zeros((), jnp.int32)), base)
    t1 = {"wv": jnp.asarray(wv)}
    s_s, m_s = step(TrainState(t1, opt.init_state(t1), jnp.zeros((), jnp.int32)), srt)
    assert float(m_r["loss"]) == pytest.approx(float(m_s["loss"]), rel=1e-5)
    np.testing.assert_allclose(
        np.asarray(s_s.tables["wv"]), np.asarray(s_r.tables["wv"]), rtol=1e-4, atol=1e-6
    )


def test_dot_f32_decomposition_exact_and_bf16_branches():
    """_dot_f32's 3-term split must reconstruct full-24-bit-mantissa f32
    values exactly (vs a float64 reference — a 2-term split would be
    ~2^-16 off), and the bf16 branch must show the single-pass ~2^-8
    rounding. Pure jnp — runs on CPU."""
    from xflow_tpu.ops.sorted_table import _dot_f32

    rng = np.random.default_rng(41)
    n, m = 64, 32
    # values exercising all 24 mantissa bits
    a = (rng.random((8, n)) * (1 + 2.0**-23) + rng.integers(1, 9, (8, n))).astype(np.float32)
    sel = rng.integers(0, n, m)
    onehot = np.zeros((n, m), np.float32)
    onehot[sel, np.arange(m)] = 1.0
    dims = (((1,), (0,)), ((), ()))

    want64 = a.astype(np.float64) @ onehot.astype(np.float64)  # exact selection
    got_exact = np.asarray(_dot_f32(jnp.asarray(a), jnp.asarray(onehot), dims, False))
    np.testing.assert_array_equal(got_exact.astype(np.float64), want64)

    got_bf16 = np.asarray(_dot_f32(jnp.asarray(a), jnp.asarray(onehot), dims, True))
    rel = np.abs(got_bf16.astype(np.float64) - want64) / np.abs(want64)
    assert rel.max() > 2.0**-10, "bf16 branch unexpectedly exact (not a single pass?)"
    assert rel.max() < 2.0**-7, "bf16 branch error exceeds one-pass rounding"


def test_table_gather_sorted_bf16_flag_smoke():
    """The bf16 opt-in branch keeps shapes/semantics (values bf16-rounded
    on TPU; on CPU the XLA fallback is exact either way)."""
    rng = np.random.default_rng(42)
    slots, mask, table = _random_case(rng)
    plan = plan_sorted_batch(slots, mask, S)
    occ = table_gather_sorted(
        jnp.asarray(table), jnp.asarray(plan.sorted_slots), jnp.asarray(plan.win_off),
        True,
    )
    n = slots.size
    assert occ.shape == (K8, plan.sorted_slots.shape[0])
    np.testing.assert_allclose(
        np.asarray(occ[:K, :n]).T, table[plan.sorted_slots[:n]], rtol=1e-2
    )

    def f(tab):
        o = table_gather_sorted(
            tab, jnp.asarray(plan.sorted_slots), jnp.asarray(plan.win_off), True
        )
        return (o[:K] * jnp.asarray(plan.sorted_mask)[None, :]).sum()

    g = jax.grad(f)(jnp.asarray(table))
    assert np.isfinite(np.asarray(g)).all()


def test_native_plan_rejects_out_of_range_slots():
    """An out-of-range slot must fail loudly: the radix sort masks each
    11-bit digit, so without validation a bad slot (possible only via a
    buggy caller — the parser hashes into range) would be silently
    aliased into a wrong window and its gradient scattered to a wrong
    table row (advisor r2)."""
    native = pytest.importorskip("xflow_tpu.data.native")
    try:
        native.get_lib()
    except Exception:
        pytest.skip("native library not built")
    from xflow_tpu.ops.sorted_table import padded_len

    for bad in (-1, S, S + 7):
        slots = np.zeros((4, 4), np.int32)
        slots[2, 1] = bad
        mask = np.ones((4, 4), np.float32)
        with pytest.raises(ValueError):
            native.native_plan_sorted(
                slots, mask, None, S, WINDOW, padded_len(slots.size)
            )


def _four_buffers(rng, cap=1024, nbuf=4):
    """`nbuf` slot-sorted buffers over the same [S, K] table, each padded
    to `cap` with slot S-1 and carrying its buffer-local window offsets
    (last entry `cap`): the fullshard engine's received stream
    (parallel/sorted_fullshard.fullshard_buffers' contract)."""
    slots, offs = [], []
    for i in range(nbuf):
        n = int(rng.integers(cap // 4, cap - CHUNK))
        real = np.sort(rng.integers(0, S, n)).astype(np.int32)
        buf = np.concatenate([real, np.full(cap - n, S - 1, np.int32)])
        off = np.searchsorted(real, np.arange(0, S + 1, WINDOW)).astype(np.int32)
        off[-1] = cap
        slots.append(buf)
        offs.append(off)
    return np.stack(slots), np.stack(offs)


@pytest.mark.parametrize("engine", ["ops", "pallas_interpret"])
@pytest.mark.parametrize("pack", [1, 8])
def test_merged_stream_matches_multi_buffer(pack, engine):
    """One slot-sorted stream merged from four buffers, through the
    single-stream gather and its VJP, equals the multi-buffer op over
    the buffers — position for position once the permutation is undone
    (forward), and slot for slot (the table gradient). `ops`: the public
    custom-VJP ops as the step calls them; `pallas_interpret`: the TPU
    kernels themselves in interpreter mode, where the offsets decide
    which positions a window's span reads and writes."""
    from contextlib import nullcontext

    from xflow_tpu.ops.sorted_table import (
        _gather_pallas_multi,
        _scatter_pallas_multi,
        pack_table,
        table_gather_sorted_multi,
        unpack_table,
    )
    from xflow_tpu.parallel.sorted_fullshard import merge_received

    pallas = engine == "pallas_interpret"
    ctx = nullcontext
    if pallas:
        pltpu = pytest.importorskip("jax.experimental.pallas.tpu")
        if not hasattr(pltpu, "force_tpu_interpret_mode"):
            pytest.skip("pallas TPU interpret mode unavailable in this jax build")
        ctx = pltpu.force_tpu_interpret_mode
    rng = np.random.default_rng(7 + pack)
    b_slots, b_off = _four_buffers(rng)
    nbuf, cap = b_slots.shape
    n = nbuf * cap
    table = rng.normal(size=(S, K)).astype(np.float32)
    jt = jnp.asarray(pack_table(table) if pack > 1 else table)
    flat = jnp.asarray(b_slots.reshape(-1))
    joff = jnp.asarray(b_off)
    # the permutation rides through the merge as a payload
    m_slots, m_off, perm = merge_received(
        jnp.asarray(b_slots), joff, jnp.arange(n, dtype=jnp.int32).reshape(nbuf, cap)
    )
    perm = np.asarray(perm)
    d_multi = rng.normal(size=(K8, n)).astype(np.float32)
    d_multi[K:] = 0.0
    d_merged = jnp.asarray(d_multi[:, perm])
    with ctx():
        if pallas:
            occ_multi = _gather_pallas_multi(jt, flat, joff, cap, False, pack)
            occ_merged = _gather_pallas(jt, m_slots, m_off, False, pack)
            g_multi = _scatter_pallas_multi(
                jnp.asarray(d_multi), flat, joff, S, K, cap, False, pack
            )
            g_merged = _scatter_pallas(d_merged, m_slots, m_off, S, K, False, pack)
        else:
            occ_multi, vjp_multi = jax.vjp(
                lambda t: table_gather_sorted_multi(t, flat, joff, False, pack), jt
            )
            occ_merged, vjp_merged = jax.vjp(
                lambda t: table_gather_sorted(t, m_slots, m_off, False, pack), jt
            )
            (g_multi,) = vjp_multi(jnp.asarray(d_multi))
            (g_merged,) = vjp_merged(d_merged)
    # forward: merged position j holds what buffer position perm[j] held
    # (interpret mode emulates the MXU's bf16 terms: rtol as above)
    np.testing.assert_allclose(
        np.asarray(occ_merged), np.asarray(occ_multi)[:, perm],
        rtol=5e-5 if pallas else 0,
    )
    np.testing.assert_allclose(
        np.asarray(occ_merged)[:K].T, table[np.asarray(m_slots)],
        rtol=5e-5 if pallas else 0,
    )
    # VJP: the same table gradient, in the table's own layout; only the
    # order of the float32 adds inside a slot differs
    assert g_merged.shape == g_multi.shape == jt.shape
    np.testing.assert_allclose(
        np.asarray(g_merged), np.asarray(g_multi), rtol=5e-5, atol=2e-5
    )
    want = np.zeros((S, K), np.float32)
    np.add.at(want, b_slots.reshape(-1), d_multi[:K].T)
    got = np.asarray(g_merged)
    np.testing.assert_allclose(
        unpack_table(got, K) if pack > 1 else got, want, rtol=5e-5, atol=2e-5
    )
