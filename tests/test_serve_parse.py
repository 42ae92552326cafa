"""A request's rows read by the native parser (`native/parser.cc
xf_parse_rows`) and by the Python one (`data/libffm.parse_line`) give
the same padded block, the same assembled batch and the same 400 —
on the rows that break parsers, and from many threads at once."""

import shutil
import threading

import numpy as np
import pytest

from xflow_tpu.config import Config, override
from xflow_tpu.serve.coalescer import PendingRequest, assemble_batch
from xflow_tpu.serve.runner import BadRequest, native_parser_ready, parse_request

pytestmark = pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")

MAX_NNZ = 8


def _dcfg(native: bool):
    return override(Config(), **{
        "data.log2_slots": 20, "data.max_nnz": MAX_NNZ, "data.hash_salt": 7,
        "data.use_native_parser": native,
    }).data


# (rows of one request, whether the native parser reads it itself; False
# where it hands the request to the Python parser, which reads it as it
# always has)
CASES = {
    "features_only": (["0:a 1:b 2:c"], True),
    "label_and_tab": (["1\t0:a 1:b", "0.5\t2:c"], True),
    "label_space_no_tab": (["1 0:a 1:b"], True),
    "vtab_formfeed_cr_separators": (["0:a\v1:b\f2:c\r3:d", "1\t4:e\r\v5:f"], True),
    "leading_trailing_newlines": (["\n\n0:a 1:b\n \n", "\n1\t2:c\n"], True),
    "embedded_newline": (["0:a\n1:b 2:c", "3:d\n"], True),
    "tab_behind_a_newline": (["\n\t0:a"], True),
    "label_only": (["1\t"], True),
    "empty_id": (["3: 4:x"], True),
    "token_without_colon": (["abc 4:x", "4:y abc"], True),
    "extra_colons": (["1:a:0.5:x 2:b:1"], True),
    "non_ascii_ids": (["0:café 1:日本 2:ß", "3:ü"], True),
    "non_ascii_field_id": (["١:a 2:b"], True),
    "non_ascii_in_field_numbers": (["1١:a ١2:b １:c ınf:d 0x1p١:e  3:f 4 :g"], True),
    "hex_inf_nan_junk_fields": (
        ["0x1A:a inf:b -inf:c nan:d junk:e 1e3:f +.5:g 0x:h", "nan(x):i 1_0:j 2e:k"], True),
    "int32_saturating_fields": (["99999999999:a -99999999999:b 2147483647:c 2147483648:d"], True),
    "nul_byte": (["0:a\x00b 1:c"], True),
    "longer_than_max_nnz": ([" ".join(f"{i}:t{i}" for i in range(3 * MAX_NNZ)), "0:a"], True),
    "whitespace_only_row": (["0:a", "  \t "], True),
    "empty_row": ([""], True),
    "no_parseable_token": (["0:a", "nothing here"], True),
    "non_string_row": (["0:a", 42], True),
    "bad_row_before_non_string": (["0:a", "junk", None], True),
    "non_string_before_bad_row": (["0:a", [1], "junk"], True),
    "lone_surrogate_in_id": (["0:a", "1:b\ud800"], False),
    "lone_surrogate_in_label": (["1\ud800\t0:a 1:b"], False),
}


def _read(rows, native):
    try:
        return parse_request(rows, _dcfg(native)), None
    except BadRequest as e:
        return None, str(e)


@pytest.mark.parametrize("case", sorted(CASES))
def test_native_and_python_parsers_give_the_same_block(case):
    rows, reads_natively = CASES[case]
    assert native_parser_ready(_dcfg(True))
    nb, nerr = _read(rows, True)
    pb, perr = _read(rows, False)
    assert nerr == perr  # the same 400, row index and message
    if perr is not None:
        return
    assert nb.native is reads_natively and pb.native is False
    for key in ("slots", "fields", "mask", "nnz"):
        assert getattr(nb, key).dtype == getattr(pb, key).dtype, key
        np.testing.assert_array_equal(getattr(nb, key), getattr(pb, key), err_msg=key)
    batches = [assemble_batch([PendingRequest(b)], 16, MAX_NNZ)[0] for b in (nb, pb)]
    for key in ("slots", "fields", "mask", "row_mask"):
        np.testing.assert_array_equal(batches[0][key], batches[1][key], err_msg=key)


def test_rows_longer_than_max_nnz_keep_their_prefix_and_count_the_rest():
    rows, _ = CASES["longer_than_max_nnz"]
    block, _ = _read(rows, True)
    assert block.nnz.tolist() == [3 * MAX_NNZ, 1]
    assert block.mask.sum(axis=1).tolist() == [MAX_NNZ, 1]
    np.testing.assert_array_equal(block.fields[0], np.arange(MAX_NNZ))


def test_threads_parsing_at_once_get_the_sequential_answers():
    rng = np.random.default_rng(5)
    requests = [
        [" ".join(f"{f}:{rng.integers(1 << 30):x}" for f in range(rng.integers(1, 12)))
         for _ in range(rng.integers(50, 300))]
        for _ in range(24)
    ]
    want = [_read(rows, False)[0] for rows in requests]
    got = [None] * len(requests)
    start = threading.Barrier(8)

    def work(k):
        start.wait()
        for i in range(k, len(requests), 8):
            got[i] = _read(requests[i], True)[0]

    threads = [threading.Thread(target=work, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for w, g in zip(want, got):
        assert g.native
        for key in ("slots", "fields", "mask", "nnz"):
            np.testing.assert_array_equal(getattr(g, key), getattr(w, key), err_msg=key)
