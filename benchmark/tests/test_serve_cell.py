"""The serve path at a size a test run can hold (CPU, one process): the
rehearsal of a serve cell end to end; `correct` true on a sound run and
false for the control and for each of the three serving faults, planted
in the reference and planted under the harness; the generator's bodies,
sizes and order; the tail's arithmetic; the per-layer readers; and the
chip's compiler asked whether the cell's table and its predict program
fit one described v5e."""

import json
import os

import numpy as np
import pytest

import run as harness
from lib import compare, counts, loadgen, serve_drive, serve_stats, weights
from lib.traffic import slots_of_ids
from reference import predict as refpredict

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as _f:
    _BENCH = json.load(_f)


def _is_serve(cell: dict) -> bool:
    with open(os.path.join(harness.HERE, "configs", cell["config"] + ".json")) as f:
        return json.load(f).get("path") == "serve"


CELLS = [w["name"] for w in _BENCH["workloads"] if _is_serve(w)]


def _run(capsys, workload, seed=2**31 + 11, trace=0):
    rc = harness.main(["--workload", workload, "--seed", str(seed), "--seconds", "1.0",
                       "--trace", str(trace), "--rehearsal"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# ---------------------------------------------------------------- a run, end to end


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_sound_run_is_correct_and_prints_no_device_metric(capsys, workload, trace):
    out = _run(capsys, workload, trace=trace)
    assert out["correct"] is True and out["metrics"] == {} and out["device"]["platform"] == "cpu"
    assert out["attempted"] > 50 and out["failed"] == 0
    assert list(out)[-1] == "compared"
    assert set(out["compared"]) == {"pctr_max_gap", "pctr_mean_gap", "window_failed_requests",
                                    "window_shed_requests", "window_compiles", "window_generations_extra"}
    assert all(v["value"] <= v["limit"] for v in out["compared"].values())
    assert out["program"]["trace_sample_rate"] == 1.0  # a traced run serves under a timed run's settings


def _broken_server(monkeypatch, alter):
    """`alter(arrays, pctr)` -> pctr, applied where the answers are
    produced: the runner's predict call."""
    made = serve_drive.Served

    def broken(pcfg):
        served = made(pcfg)
        predict = served.runner.predict

        def bad(arrays):
            p, gen = predict(arrays)
            return alter(arrays, np.array(p)), gen

        served.runner.predict = bad
        return served

    monkeypatch.setattr(serve_drive, "Served", broken)


@pytest.mark.parametrize("workload", CELLS)
def test_fault_answers_rotated_by_one_row(capsys, monkeypatch, workload):
    def rotate(arrays, p):
        n = int(arrays["row_mask"].sum())
        p[:n] = np.roll(p[:n], 1)
        return p

    _broken_server(monkeypatch, rotate)
    out = _run(capsys, workload)
    assert out["correct"] is False
    assert out["compared"]["pctr_mean_gap"]["value"] > 10 * out["compared"]["pctr_mean_gap"]["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_fault_one_answer_altered(capsys, monkeypatch, workload):
    def nudge(arrays, p):
        p[0] += 1e-3
        return p

    _broken_server(monkeypatch, nudge)
    out = _run(capsys, workload)
    assert out["correct"] is False
    assert out["compared"]["pctr_max_gap"]["value"] > 10 * out["compared"]["pctr_max_gap"]["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_fault_table_from_another_seed(capsys, monkeypatch, workload):
    make = weights.chunk_table_fn
    monkeypatch.setattr(weights, "chunk_table_fn", lambda seed, *a: make(seed + 1, *a))
    out = _run(capsys, workload)
    assert out["correct"] is False
    assert out["compared"]["pctr_mean_gap"]["value"] > 0.01


@pytest.mark.parametrize("workload", CELLS)
def test_fault_one_field_left_out(capsys, monkeypatch, workload):
    made = serve_drive.Served

    def broken(pcfg):
        served = made(pcfg)
        predict = served.runner.predict

        def bad(arrays):
            mask = arrays["mask"].copy()
            mask[:, -1] = 0.0
            return predict({**arrays, "mask": mask})

        served.runner.predict = bad
        return served

    monkeypatch.setattr(serve_drive, "Served", broken)
    out = _run(capsys, workload)
    assert out["correct"] is False
    assert out["compared"]["pctr_mean_gap"]["value"] > 10 * out["compared"]["pctr_mean_gap"]["limit"]


# ---------------------------------------------------------------- the control and the faults, in the reference


@pytest.fixture(scope="module")
def small():
    _, cell, cfg, traffic = harness.load_cell(CELLS[0], rehearsal=True)
    pool = loadgen.make_pool(2**31 + 23, cfg, traffic)
    ref = refpredict.serve_pctr(cfg, 2**31 + 23, pool["ids"], slots_of_ids, weights.rows_numpy)
    return cfg, traffic, pool, ref, compare.load_limits(harness.HERE, cfg)


def _judge(served, ref, limits):
    numbers = serve_stats.pctr_gaps(served, ref)
    numbers.update(window_failed_requests=0, window_shed_requests=0, window_compiles=0,
                   window_generations_extra=0)
    return compare.judge(numbers, limits)


def test_reference_against_itself_is_correct(small):
    cfg, _, pool, ref, limits = small
    assert ref.min() < 0.4 and ref.max() > 0.6 and 0.05 < ref.std() < 0.3  # pCTRs spread, not all one half
    ok, _ = _judge(ref, ref, limits)
    assert ok


def test_control_lower_precision_is_not_correct(small):
    cfg, _, pool, ref, limits = small
    low = refpredict.serve_pctr(cfg, 2**31 + 23, pool["ids"], slots_of_ids, weights.rows_numpy, dtype="bfloat16")
    ok, table = _judge(low, ref, limits)
    assert not ok, table
    assert table["pctr_mean_gap"]["value"] > 3 * table["pctr_mean_gap"]["limit"]
    assert table["pctr_max_gap"]["value"] > 3 * table["pctr_max_gap"]["limit"]


@pytest.mark.parametrize("fault", refpredict.FAULTS)
def test_faults_planted_in_the_reference_fail(small, fault):
    cfg, _, pool, ref, limits = small
    bad = refpredict.serve_pctr(cfg, 2**31 + 23, pool["ids"], slots_of_ids, weights.rows_numpy, fault=fault,
                                offsets=pool["offsets"])
    ok, table = _judge(bad, ref, limits)
    assert not ok, table
    assert table["pctr_mean_gap"]["value"] > 10 * table["pctr_mean_gap"]["limit"]


@pytest.mark.parametrize("name", ["window_failed_requests", "window_shed_requests", "window_compiles",
                                  "window_generations_extra"])
def test_an_exact_number_off_by_one_is_not_correct(small, name):
    *_, ref, limits = small
    numbers = serve_stats.pctr_gaps(ref, ref)
    numbers.update(window_failed_requests=0, window_shed_requests=0, window_compiles=0, window_generations_extra=0)
    numbers[name] = 1
    assert not compare.judge(numbers, limits)[0]


def test_no_answer_is_not_correct():
    assert serve_stats.pctr_gaps(np.zeros(0), np.zeros(0))["pctr_max_gap"] == 1.0
    assert serve_stats.pctr_gaps(np.zeros(3), np.zeros(4))["pctr_mean_gap"] == 1.0


def test_the_clamped_sigmoid_is_the_upstreams():
    p = refpredict.pctr_of_logits(np.array([-31.0, -30.0, 0.0, 30.0, 31.0]))
    assert p[0] == 1e-6 and p[4] == 1.0 and p[2] == 0.5
    assert p[1] == pytest.approx(1 / (1 + np.exp(30.0))) and p[3] == pytest.approx(1 / (1 + np.exp(-30.0)))


# ---------------------------------------------------------------- the generator


def test_pool_is_the_seeds_and_every_seed_has_the_same_sizes():
    _, _, cfg, traffic = harness.load_cell(CELLS[0], rehearsal=True)
    a, b, c = (loadgen.make_pool(s, cfg, traffic) for s in (2**31 + 5, 2**31 + 5, 2**31 + 6))
    assert np.array_equal(a["sizes"], b["sizes"]) and np.array_equal(a["ids"], b["ids"])
    assert loadgen.make_bodies(a) == loadgen.make_bodies(b)
    assert not np.array_equal(a["sizes"], c["sizes"]) and not np.array_equal(a["ids"], c["ids"])
    assert np.array_equal(np.sort(a["sizes"]), np.sort(c["sizes"]))  # the same work, in another order
    assert loadgen.make_bodies(a) != loadgen.make_bodies(c)


def _traffic_files():
    return sorted({w["traffic"] for w in _BENCH["workloads"] if _is_serve(w)})


@pytest.mark.parametrize("name", _traffic_files())
def test_sizes_follow_the_traffic_file(name):
    with open(os.path.join(harness.HERE, "traffic", name + ".json")) as f:
        traffic = json.load(f)
    spec = traffic["rows_per_request"]
    sizes = loadgen.request_sizes(spec, int(traffic["pool_requests"]))
    assert sizes.min() == spec["min"] and sizes.max() == spec["max"]
    assert abs(np.median(sizes) - spec["median"]) <= 1


def test_bodies_parse_back_through_the_programs_request_parser():
    from xflow_tpu.config import Config, override
    from xflow_tpu.serve.runner import parse_rows

    _, _, cfg, traffic = harness.load_cell(CELLS[0], rehearsal=True)
    pool = loadgen.make_pool(2**31 + 9, cfg, traffic)
    bodies = loadgen.make_bodies(pool)
    assert len(bodies) == int(traffic["pool_requests"])
    dcfg = override(Config(), **{"data.log2_slots": int(cfg["log2_slots"]), "data.max_nnz": 32}).data
    for k in (0, 7, len(bodies) - 1):
        rows = json.loads(bodies[k])["rows"]
        lo, hi = pool["offsets"][k], pool["offsets"][k + 1]
        assert len(rows) == pool["sizes"][k] == hi - lo
        fields, slots = parse_rows(rows, dcfg)
        assert np.array_equal(np.stack(slots), slots_of_ids(pool["ids"][lo:hi], int(cfg["log2_slots"])))
        assert all(np.array_equal(f, np.arange(32)) for f in fields)


def test_arrivals_are_the_seeds_order_of_the_same_gaps_at_the_rate():
    a, b, c = loadgen.arrivals(7, 500.0, 20.0), loadgen.arrivals(7, 500.0, 20.0), loadgen.arrivals(8, 500.0, 20.0)
    assert np.array_equal(a, b) and not np.array_equal(a[:50], c[:50])
    assert len(a) == len(c) == 10000 and 0.0 < a[0] and a[-1] < 20.0 and np.all(np.diff(a) > 0)
    gaps_a, gaps_c = np.diff(a, prepend=0.0), np.diff(c, prepend=0.0)
    assert np.allclose(np.sort(gaps_a), np.sort(gaps_c), rtol=1e-9, atol=1e-12)  # the same work, in another order
    assert abs(gaps_a.std() / gaps_a.mean() - 1.0) < 0.02  # an exponential's gaps: a Poisson process


# ---------------------------------------------------------------- the arithmetic


def _log(done, status=None, n=None, seconds=10.0):
    done = np.asarray(done, np.float64)
    k = len(done)
    return {"pool_index": np.arange(k), "due_s": np.zeros(k), "sent_s": np.zeros(k), "done_s": done,
            "status": np.full(k, 200) if status is None else np.asarray(status),
            "generation": np.ones(k, np.int64), "n_pctr": np.full(k, 2) if n is None else np.asarray(n),
            "pctr": np.zeros(2 * k), "seconds": np.float64(seconds), "closed_s": np.float64(done.max()),
            "offered": np.int64(k)}


def test_p99_counts_a_failed_request_as_slowest():
    sound = serve_stats.window_stats(_log(np.linspace(0.001, 0.2, 200)))
    assert sound["p99_ms"] == pytest.approx(1e3 * np.linspace(0.001, 0.2, 200)[197])
    status = np.full(200, 200)
    status[:3] = [503, 599, 598]  # shed, transport, not one pCTR a row: answered fast, and failed
    broken = serve_stats.window_stats(_log(np.linspace(0.001, 0.2, 200), status))
    assert broken["failed"] == 3 and broken["shed"] == 1
    assert broken["p99_ms"] == serve_stats.FAILED_MS and broken["max_ms"] == serve_stats.FAILED_MS
    assert broken["p50_ms"] > sound["p50_ms"]


def test_rate_counts_rows_answered_inside_the_window_over_its_seconds():
    st = serve_stats.window_stats(_log([1.0, 9.9, 10.4], n=[5, 7, 100]))
    assert st["rows_in_window"] == 12 and st["rows_per_s"] == pytest.approx(1.2)
    assert st["requests"] == 3 and st["answered"] == 3 and st["rows_answered"] == 112
    assert st["p99_ms"] == pytest.approx(10400.0)  # a tail is the tail of all requests sent


def test_answered_rows_maps_every_answer_to_its_reference_row():
    pool = {"sizes": np.array([2, 3, 1, 4]), "offsets": np.array([0, 2, 5, 6, 10]),
            "ids": np.arange(10)[:, None]}
    log = {"status": np.array([200, 503, 200, 200]), "pool_index": np.array([3, 0, 1, 3])}
    entries, row_of_answer, offsets = serve_stats.answered_rows(log, pool)
    assert entries.tolist() == [1, 3] and offsets.tolist() == [0, 3, 7]
    ids = serve_stats.entry_ids(pool, entries)[:, 0]
    assert ids[row_of_answer].tolist() == [6, 7, 8, 9, 2, 3, 4, 6, 7, 8, 9]


def test_predict_needs_is_a_lower_bound_of_the_bytes_a_batch_touches():
    needs = counts.predict_needs(1000.0, 4096.0, 11)
    assert needs["bytes"] == 1000 * 44 + 4096 * (44 + 12) and needs["flops"] == 4096 * 33


def test_per_layer_readers_read_a_traced_serve_run():
    spans = {"queue": [{"dur_ms": float(k)} for k in range(1, 201)],
             "device_batch": [{"dur_ms": 20.0}, {"dur_ms": 22.0}, {"dur_ms": 30.0}]}
    windows = [{"batches": 10, "rows": 1280, "batch_fill": 0.5}, {"batches": 10, "rows": 2560, "batch_fill": 1.0}]
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = {"serve": {"windows": windows, "spans": spans},
           "window": {"closed_s": 2.0, "requests": 2000, "p99_ms": 150.0, "p95_ms": 120.0, "p50_ms": 90.0},
           "shape": lambda: {"distinct_slots": 3000.0, "occurrences": 6144.0, "batches": 20}, "width": 11,
           "peak": peak, "memory_peak_bytes": 5_906_000_000,
           "trace": {"devices": 1, "busy_s": 1.5, "window_s": 6.0, "module_runs": {"jit_step": [100, 1.5]}}}
    read = lambda name: harness.load_metric(name).read(run)  # noqa: E731
    assert read("serve_p99_ms") == 150.0 and read("serve_p95_ms") == 120.0 and read("serve_p50_ms") == 90.0
    assert read("serve_queue_wait_p99_ms") == 198.0
    assert read("serve_device_p50_ms") == 22.0
    assert read("serve_batch_fill_pct") == pytest.approx(100.0 * 3840 / 5120)
    assert read("serve_device_idle_pct") == pytest.approx(75.0)
    assert read("serve_hbm_peak_gb") == pytest.approx(5.906)
    least = (3000 * 44 + 6144 * 56) / 819e9
    assert read("serve_predict_roofline") == pytest.approx(100.0 * least / 0.015)
    assert read("serve_step_mfu") == pytest.approx(100.0 * 20 * least / 2.0)
    empty = {"serve": {"windows": [], "spans": {}}, "window": {"closed_s": 2.0}, "shape": dict, "width": 11,
             "peak": peak, "memory_peak_bytes": None, "trace": None}
    for name in sorted(f[:-3] for f in os.listdir(os.path.join(harness.HERE, "metrics")) if f.startswith("serve_")):
        assert harness.load_metric(name).read(empty) is None, name


def test_module_runs_counts_whole_executions():
    from lib import trace

    t = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_step(12)", 0.0, 2e6], ["jit_step(12)", 5e6, 4e6], ["jit_x(3)", 9e6, 1e6]]},
        {"name": "XLA Ops", "events": [["fusion.1", 0.0, 1e6]]}]}]}
    runs = trace.module_runs(t, trace.load_names(harness.HERE))
    assert runs == {"jit_step": [2, pytest.approx(0.006)], "jit_x": [1, pytest.approx(0.001)]}


# ---------------------------------------------------------------- the size, asked of the chip's compiler


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _predict_memory(cfg: dict, log2_slots: int, one_chip):
    """The predict program of the cell's configuration at `log2_slots`,
    compiled for a described v5e at the server's batch shape."""
    import jax
    import jax.numpy as jnp

    from xflow_tpu.models import get_model
    from xflow_tpu.models.predict import make_predict_fn
    from xflow_tpu.optim import get_optimizer
    from xflow_tpu.train.state import init_state

    pcfg = serve_drive.program_config(dict(cfg, log2_slots=log2_slots), "ckpt", "sock")
    model = get_model(pcfg.model.name)
    abstract = jax.eval_shape(lambda: init_state(model, get_optimizer(pcfg.optim.name), pcfg))
    tables = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip) for k, v in abstract.tables.items()}
    B, N = pcfg.serve.max_batch, pcfg.data.max_nnz
    batch = {k: jax.ShapeDtypeStruct((B, N), t, sharding=one_chip)
             for k, t in (("slots", jnp.int32), ("fields", jnp.int32), ("mask", jnp.float32))}
    batch["row_mask"] = jax.ShapeDtypeStruct((B,), jnp.float32, sharding=one_chip)
    return make_predict_fn(model, pcfg).lower(tables, batch).compile().memory_analysis()


@pytest.mark.parametrize("workload", CELLS)
def test_the_cells_table_and_predict_program_fit_one_v5e(workload, one_chip, no_persistent_cache):
    _, _, cfg, _ = harness.load_cell(workload, rehearsal=False)
    m = _predict_memory(cfg, int(cfg["log2_slots"]), one_chip)
    table = (1 << int(cfg["log2_slots"])) * (1 + int(cfg["v_dim"])) * 4
    assert m.argument_size_in_bytes >= table  # the table resident, in the client's default layout
    assert table >= 0.25 * 16 * 2**30  # the driver's floor by the allocator's peak
    assert m.argument_size_in_bytes + m.temp_size_in_bytes <= 15.75e9
    # the next size cannot be served: the cell is the largest table one chip holds
    try:
        bigger = _predict_memory(cfg, int(cfg["log2_slots"]) + 1, one_chip)
    except Exception:  # noqa: BLE001 - the compiler's own refusal
        return
    assert bigger.argument_size_in_bytes + bigger.temp_size_in_bytes > 15.75e9
