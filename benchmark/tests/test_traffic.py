"""The generator: the text it writes parses back, through the program's
native parser, to the ids and labels it drew; its hash rule is the
program's; the seed's weights are the same bits on the host and in XLA."""

import os

import numpy as np
import pytest

from lib import traffic, weights

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAFFIC = traffic.load_traffic(HERE, "text-zipf")
CFG = {"batch_size": 256, "num_fields": 32, "log2_slots": 20}


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    return traffic.make_run_data(str(tmp_path_factory.mktemp("d")), 2**31 + 77, CFG, dict(TRAFFIC, steps_per_pass=3))


def test_rows_parse_back_through_the_native_parser(data):
    from xflow_tpu.config import Config, override
    from xflow_tpu.data.pipeline import batch_iterator

    pcfg = override(Config(), **{"data.log2_slots": 20, "data.max_nnz": 32, "data.batch_size": 256,
                                 "model.num_fields": 32, "data.cache": "off"})
    shard = data["first"][0]
    batches = list(batch_iterator(shard["path"], pcfg.data))
    assert len(batches) == 1 and batches[0].num_rows == 256
    b = batches[0]
    assert np.array_equal(b.slots, traffic.slots_of_ids(shard["ids"], 20))
    assert np.array_equal(b.labels, shard["labels"].astype(np.float32))
    assert np.array_equal(b.fields, np.tile(np.arange(32, dtype=np.int32), (256, 1)))
    assert b.mask.min() == 1.0
    rows = sum(x.num_rows for x in batch_iterator(data["train_prefix"] + "-00000", pcfg.data))
    assert rows == data["rows_per_pass"] == 3 * 256


def test_text_is_libffm(data):
    with open(data["first"][1]["path"]) as f:
        line = f.readline().rstrip("\n")
    label, rest = line.split("\t")
    toks = rest.split(" ")
    assert label in ("0", "1") and len(toks) == 32
    ids = data["first"][1]["ids"][0]
    assert toks == [f"{f}:{ids[f]}:1" for f in range(32)]


def test_hash_rule_is_the_programs():
    from xflow_tpu.hashing import fnv1a64, slot_of

    ids = np.array([0, 7, 10, 99, 100, 123456789, 2**32 - 1], np.uint64)
    mine = traffic.slots_of_ids(ids, 29)
    theirs = [slot_of(fnv1a64(str(int(i)).encode()), 29) for i in ids]
    assert mine.tolist() == theirs


def test_same_seed_same_rows_and_seeds_differ():
    a = traffic.draw_rows(2**31 + 5, 1, 1000, 32, 1 << 15, TRAFFIC)
    b = traffic.draw_rows(2**31 + 5, 1, 1000, 32, 1 << 15, TRAFFIC)
    c = traffic.draw_rows(2**31 + 6, 1, 1000, 32, 1 << 15, TRAFFIC)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert len({tuple(r) for r in a[0]}) == 1000  # rows all differ


def test_powerlaw_is_skewed_and_uniform_is_not():
    rng = np.random.default_rng(1)
    z = traffic.draw_ranks(rng, 200_000, {"dist": "powerlaw", "alpha": 1.1}, 1 << 20)
    u = traffic.draw_ranks(rng, 200_000, {"dist": "uniform"}, 1 << 20)
    assert z.min() >= 0 and z.max() < 1 << 20 and u.max() < 1 << 20
    assert (z == 0).mean() > 0.05 > (u < 1000).mean()
    assert np.unique(z).size < 0.5 * np.unique(u).size


def test_weights_same_bits_on_host_and_in_xla():
    import jax

    seed, width, pack, slots = 2**31 + 9, 11, 8, 4096
    table = np.asarray(jax.jit(weights.packed_table_fn(seed, slots, width, pack, 0.01))())
    assert table.shape == (slots // pack, pack * width)
    logical = table.reshape(slots, width)  # row-major: slot s at row s // 8, columns (s % 8) * 11 + j
    pick = np.array([0, 1, 7, 8, 9, 4095])
    assert np.array_equal(logical[pick], weights.rows_numpy(seed, pick, width, 0.01))
    assert np.all(logical[:, 0] == 0.0)
    v = logical[:, 1:]
    assert abs(v.std() - 0.01) < 2e-4 and abs(v.mean()) < 2e-4
