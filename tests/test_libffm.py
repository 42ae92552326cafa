import numpy as np
import pytest

from xflow_tpu.data.libffm import available_shards, iter_examples, parse_line, shard_path
from xflow_tpu.data.pipeline import examples_to_batches
from xflow_tpu.data.synth import generate_shards
from xflow_tpu.hashing import fnv1a64, slot_of

LOG2 = 20


def test_parse_line_basic():
    ex = parse_line("1\t0:0:0.3651 2:1163:0.3651 17:2434:0.50000", LOG2)
    label, fields, slots = ex
    assert label == 1.0
    assert list(fields) == [0, 2, 17]
    assert slots[1] == slot_of(fnv1a64(b"1163"), LOG2)


def test_label_threshold_matches_reference():
    # load_data_from_disk.cc:131-134: y=1 iff atof(label) > 1e-7
    assert parse_line("0.5\t0:1:1.0", LOG2)[0] == 1.0
    assert parse_line("0\t0:1:1.0", LOG2)[0] == 0.0
    assert parse_line("-1\t0:1:1.0", LOG2)[0] == 0.0
    assert parse_line("0.0000000001\t0:1:1.0", LOG2)[0] == 0.0


def test_value_field_is_ignored():
    a = parse_line("1\t3:42:0.111", LOG2)
    b = parse_line("1\t3:42:99.9", LOG2)
    assert a[2][0] == b[2][0]


def test_feature_id_hashed_as_string():
    # "7" and "07" are distinct strings → distinct keys (reference hashes
    # the token string, not the parsed integer)
    a = parse_line("1\t0:7:1", LOG2)[2][0]
    b = parse_line("1\t0:07:1", LOG2)[2][0]
    assert a != b


@pytest.mark.parametrize("tok", [
    "3", " 3\n", "-2.5e1", ".5", "1_0", "0x1A", "0x1p3", "inf", "-Infinity",
    "nan(x)", "junk", "2e", "1e5000",
    "١", "1١", "١2", "１", " 3", "ınf", "0x1p١", "1e١",
])
def test_strtod_reads_what_c_strtod_reads(tok):
    # the native parser reads labels and field ids with strtod; the
    # Python path must read the same number, non-ASCII text included
    import ctypes
    import math

    from xflow_tpu.data.libffm import _strtod

    libc = ctypes.CDLL(None)
    libc.strtod.restype = ctypes.c_double
    libc.strtod.argtypes = [ctypes.c_char_p, ctypes.c_void_p]
    want = libc.strtod(tok.encode("utf-8"), None)
    got = _strtod(tok)
    assert (math.isnan(got) and math.isnan(want)) or got == want, tok


def test_shard_path_convention():
    assert shard_path("/x/train", 0) == "/x/train-00000"
    assert shard_path("/x/train", 42) == "/x/train-00042"


def test_synth_roundtrip_and_batching(tmp_path):
    prefix = str(tmp_path / "synth")
    paths = generate_shards(prefix, num_shards=2, rows_per_shard=57, seed=3)
    assert paths == available_shards(prefix)
    examples = list(iter_examples(paths[0], LOG2))
    assert len(examples) == 57
    label, fields, slots = examples[0]
    assert fields.shape == slots.shape == (18,)
    batches = list(examples_to_batches(iter(examples), batch_size=16, max_nnz=32))
    assert len(batches) == 4  # 3 full + 1 padded partial
    assert batches[-1].num_rows == 57 - 48
    full = batches[0]
    assert full.slots.shape == (16, 32)
    assert full.mask[:, :18].all() and not full.mask[:, 18:].any()
    assert full.row_mask.all()


def test_drop_remainder():
    examples = [(1.0, np.array([0], np.int32), np.array([5], np.int32))] * 10
    batches = list(examples_to_batches(iter(examples), 4, 8, drop_remainder=True))
    assert len(batches) == 2


def test_synth_deterministic(tmp_path):
    p1 = generate_shards(str(tmp_path / "a"), 1, 20, seed=7)[0]
    p2 = generate_shards(str(tmp_path / "b"), 1, 20, seed=7)[0]
    assert open(p1).read() == open(p2).read()
