"""The packed serving batch (serve/coalescer.py `assemble_packed`,
serve/runner.py `make_packed_predict_fn`): a solo runner assembles a
batch into one int32 vector with one native call, and its program
unpacks it. What it answers must be what the dict path answers, bit for
bit: the dict path is the one a mesh runner, or a runner without the
native library, still takes.

Each case is one group of requests through both runners of a
three-rung ladder: native and Python-parsed blocks, blocks sliced out
of a larger block, and the all-padding group `warmup()` compiles with.
The replies compared are the worker's own, timing fields aside."""

import json

import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.serve import runner as runner_mod
from xflow_tpu.serve.coalescer import (
    PackedBatch,
    PendingRequest,
    RowBlock,
    assemble_batch,
    assemble_packed,
    pack_arrays,
)
from xflow_tpu.serve.runner import ServeRunner, parse_request
from xflow_tpu.train.checkpoint import write_flat

LOG2_SLOTS, K, MAX_NNZ = 10, 11, 8  # FM's row: w and v_dim 10
LADDER = (8, 16, 32)
TIMING = ("queue_ms", "total_ms")


def _cfg(ckpt_dir, native_parser=True):
    return override(Config(), **{
        "model.name": "fm",
        "model.num_fields": 5,
        "data.log2_slots": LOG2_SLOTS,
        "data.max_nnz": MAX_NNZ,
        "data.use_native_parser": native_parser,
        "train.checkpoint_dir": str(ckpt_dir),
        "serve.max_batch": LADDER[-1],
        "serve.ladder": ",".join(map(str, LADDER)),
        "serve.window_ms": 1e6,  # a group leaves the batcher when it closes
    })


@pytest.fixture(scope="module")
def runners(tmp_path_factory):
    """(cfg, packed runner, dict runner) over one committed table."""
    ck = tmp_path_factory.mktemp("pack_ck")
    rng = np.random.default_rng(3)
    table = rng.normal(0, 0.3, (1 << LOG2_SLOTS, K)).astype(np.float32)
    write_flat(str(ck), {"tables/wv": table, "step": np.asarray(1, np.int32)}, 1)
    cfg = _cfg(ck)
    packed = ServeRunner(cfg)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(runner_mod, "native_assembly_ready", lambda: False)
        plain = ServeRunner(cfg)
    assert packed.packs_batches and not plain.packs_batches
    for r in (packed, plain):
        r.load()
        assert r.warmup() == len(LADDER)
    return cfg, packed, plain


def _rows(rng, n):
    """n libffm feature rows over 5 fields; some longer than MAX_NNZ."""
    return [
        " ".join(f"{rng.integers(5)}:{rng.integers(1 << 20):x}:1"
                 for _ in range(rng.integers(1, 2 * MAX_NNZ)))
        for _ in range(n)
    ]


def _sizes(n_requests: int, rung_index: int, rng) -> list:
    """Row counts of n_requests requests whose total needs LADDER[rung_index]."""
    lo = LADDER[rung_index - 1] + 1 if rung_index else 1
    total = int(rng.integers(max(lo, n_requests), LADDER[rung_index] + 1))
    cuts = np.sort(rng.choice(np.arange(1, total), n_requests - 1, replace=False))
    return np.diff(np.concatenate([[0], cuts, [total]])).tolist()


def _group(cfg, n_requests: int, source: str) -> list:
    rng = np.random.default_rng(100 * n_requests + len(source))
    sizes = _sizes(n_requests, n_requests % len(LADDER), rng)
    native, python = cfg.data, _cfg("", native_parser=False).data
    if source == "part":  # views into one block of every request's rows
        whole = parse_request(_rows(rng, sum(sizes)), native if n_requests % 2 else python)
        edges = np.concatenate([[0], np.cumsum(sizes)])
        blocks = [whole.part(lo, hi) for lo, hi in zip(edges[:-1], edges[1:])]
    else:
        dcfg = native if source == "native" else python
        blocks = [parse_request(_rows(rng, n), dcfg) for n in sizes]
    assert all(b.native is (source == "native") for b in blocks) or source == "part"
    return [PendingRequest(b) for b in blocks]


def _bits(p) -> np.ndarray:
    return np.asarray(p, np.float32).view(np.uint32)


def _replies(cfg, runner, blocks: list) -> list:
    """The worker's replies to `blocks`, sent as one group, as JSON
    without their timing fields."""
    from xflow_tpu.serve.server import ServeApp

    app = ServeApp(cfg, runner)
    futures = [app.batcher.submit(b) for b in blocks]
    app.batcher.close()  # the whole group is releasable at once
    app.start()
    try:
        out = [f.result(timeout=60) for f in futures]
    finally:
        app.close()
    return [json.dumps({k: v for k, v in r.items() if k not in TIMING}) for r in out]


@pytest.mark.parametrize("source", ["native", "python", "part"])
@pytest.mark.parametrize("n_requests", range(1, 9))
def test_packed_batch_answers_as_the_dict_batch(runners, n_requests, source):
    cfg, packed, plain = runners
    group = _group(cfg, n_requests, source)
    rows = sum(r.num_rows for r in group)
    rung = next(r for r in LADDER if rows <= r)
    assert rung == LADDER[n_requests % len(LADDER)]
    batch, spans = packed.assemble(group, rung)
    arrays, want_spans = assemble_batch(group, rung, MAX_NNZ)
    assert isinstance(batch, PackedBatch) and batch.num_rows == rows
    assert [s[1:] for s in spans] == [s[1:] for s in want_spans]
    for key, want in arrays.items():
        assert batch[key].dtype == want.dtype, key
        np.testing.assert_array_equal(batch[key], want, err_msg=key)
    p, _ = packed.predict(batch)
    q, _ = plain.predict(arrays)
    np.testing.assert_array_equal(_bits(p), _bits(q))
    blocks = [r.block for r in group]
    assert _replies(cfg, packed, blocks) == _replies(cfg, plain, blocks)


@pytest.mark.parametrize("rung", LADDER)
def test_the_warmup_group_is_all_padding(runners, rung):
    _, packed, plain = runners
    batch, spans = packed.assemble([], rung)
    assert spans == [] and batch.num_rows == 0
    assert not batch.buf.any() and batch.buf.size == 3 * rung * MAX_NNZ + 1
    p, _ = packed.predict(batch)
    q, _ = plain.predict(assemble_batch([], rung, MAX_NNZ)[0])
    np.testing.assert_array_equal(_bits(p), _bits(q))


def test_a_dict_given_to_the_packed_runner_is_packed(runners):
    """Direct `predict` callers keep handing dicts: the packed runner
    packs them into the same vector, and answers them alike."""
    cfg, packed, plain = runners
    group = _group(cfg, 3, "native")
    arrays, _ = assemble_batch(group, 8, MAX_NNZ)
    batch, _ = assemble_packed(group, 8, MAX_NNZ)
    np.testing.assert_array_equal(pack_arrays(arrays).buf, batch.buf)
    np.testing.assert_array_equal(_bits(packed.predict(arrays)[0]),
                                  _bits(plain.predict(arrays)[0]))


def test_predict_rows_takes_the_packed_path(runners, monkeypatch):
    cfg, packed, plain = runners
    rows = _rows(np.random.default_rng(9), 70)  # three chunks of max_batch
    seen = []
    predict = packed.predict
    monkeypatch.setattr(packed, "predict", lambda b: seen.append(type(b)) or predict(b))
    p, _ = packed.predict_rows(rows)
    q, _ = plain.predict_rows(rows)
    assert seen == [PackedBatch] * 3
    np.testing.assert_array_equal(_bits(p), _bits(q))


def test_blocks_that_do_not_fit_are_refused(runners):
    group = [PendingRequest(RowBlock.from_rows([np.arange(2, dtype=np.int32)] * 9,
                                               [np.arange(2, dtype=np.int32)] * 9, MAX_NNZ))]
    with pytest.raises(ValueError, match="batch_size"):
        assemble_packed(group, 8, MAX_NNZ)
    wide = RowBlock.from_rows([np.arange(2, dtype=np.int32)], [np.arange(2, dtype=np.int32)],
                              MAX_NNZ + 1)
    with pytest.raises(ValueError, match="C-contiguous"):
        assemble_packed([PendingRequest(wide)], 8, MAX_NNZ)
