"""Sanitizer runs for the native data plane (SURVEY.md §5 "race
detection / sanitizers": the reference has none; our plan gives the
concurrent C++ components — the MT parser pool, native/parser.cc
(mutex/condvar/atomics), and the serving entry point xf_parse_rows
that request threads call at once — TSan and ASan+UBSan runs), and the
serving batch's assembler xf_assemble_rows.

Each case rebuilds parser.cc with `-fsanitize=...` (the flag joins the
build-cache key, data/native.py _build_lib) and exercises the
multi-threaded parser against the sequential one in a SUBPROCESS with
the sanitizer runtime LD_PRELOADed (the host python is uninstrumented,
so the runtime must be loaded first) and halt_on_error set: any data
race / heap error / UB exits nonzero and fails the test. Auto-skips
when the toolchain lacks the runtime libraries.
"""

import os
import subprocess
import sys
import textwrap

import pytest

from xflow_tpu.data.synth import generate_shards

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _runtime_for(sanitize: str):
    lib = "libtsan.so" if sanitize.startswith("thread") else "libasan.so"
    try:
        out = subprocess.run(
            ["gcc", f"-print-file-name={lib}"], capture_output=True, text=True
        ).stdout.strip()
    except FileNotFoundError:
        return None
    return out if out and os.path.isabs(out) and os.path.exists(out) else None


DRIVER = textwrap.dedent("""
    import dataclasses, sys
    import numpy as np
    from xflow_tpu.config import DataConfig
    from xflow_tpu.data.native import native_batch_iterator, native_count_rows
    path = sys.argv[1]
    seq = dataclasses.replace(
        DataConfig(log2_slots=16, max_nnz=10),
        parser_threads=1, block_bytes=4096,
    )
    mt = dataclasses.replace(seq, parser_threads=4)
    a = list(native_batch_iterator(path, seq, 64))
    b = list(native_batch_iterator(path, mt, 64))
    assert len(a) == len(b) and len(a) > 0, (len(a), len(b))
    for i, (x, y) in enumerate(zip(a, b)):
        # plain elementwise compares, NOT np.testing: lazily importing
        # numpy.testing inside a TSan-preloaded process deadlocks on
        # some kernels (observed on 4.4 — zero CPU until timeout)
        for field in ("slots", "fields", "mask", "labels"):
            xa, ya = getattr(x, field), getattr(y, field)
            assert (xa == ya).all(), (i, field)
    assert native_count_rows(path, 4096) == sum(
        int(x.row_mask.sum()) for x in a
    )
    # the serving entry point: one buffer, rows by offsets, four
    # threads in the library at once (ctypes drops the GIL)
    import threading
    from xflow_tpu.data.native import native_parse_rows
    rows = open(path, "rb").read().splitlines()
    buf = b"".join(rows)
    off = np.concatenate([[0], np.cumsum([len(r) for r in rows])])
    want = native_parse_rows(buf, off, seq)
    assert want[0] == len(rows), want[0]
    got = [None] * 4
    def parse(k):
        got[k] = native_parse_rows(buf, off, seq)
    threads = [threading.Thread(target=parse, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for g in got:
        assert g[0] == want[0] and all((x == y).all() for x, y in zip(g[1:], want[1:]))
    print("SANITIZED_PARITY_OK", len(a))
""")


# the serving batch's assembler (xf_assemble_rows, called with the GIL
# held): a group that fills its rung exactly, one one-row request, and
# blocks sliced out of a larger one, each against numpy's assemble_batch;
# then four threads assembling from the same blocks at once
ASSEMBLE_DRIVER = textwrap.dedent("""
    import sys, threading
    import numpy as np
    from xflow_tpu.config import DataConfig
    from xflow_tpu.serve.coalescer import PendingRequest, assemble_batch, assemble_packed
    from xflow_tpu.serve.runner import parse_request
    k = 10
    rows = open(sys.argv[1]).read().splitlines()[:300]
    big = parse_request(rows, DataConfig(log2_slots=16, max_nnz=k))
    assert big.native and big.num_rows == 300
    cases = {
        "fills_the_rung": ([(0, 30), (30, 64)], 64),
        "one_row": ([(5, 6)], 8),
        "sliced": ([(10, 17), (100, 140), (299, 300)], 64),
    }
    for name, (parts, rung) in cases.items():
        group = [PendingRequest(big.part(lo, hi)) for lo, hi in parts]
        packed, _ = assemble_packed(group, rung, k)
        want, _ = assemble_batch(group, rung, k)
        for key in ("slots", "fields", "mask", "row_mask"):
            got = packed[key]
            assert got.dtype == want[key].dtype, (name, key)
            assert (got.view(np.uint32) == want[key].view(np.uint32)).all(), (name, key)
    group = [PendingRequest(big.part(lo, hi)) for lo, hi in cases["sliced"][0]]
    want = assemble_packed(group, 64, k)[0].buf
    got = [None] * 4
    def assemble(i):
        got[i] = assemble_packed(group, 64, k)[0].buf
    threads = [threading.Thread(target=assemble, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all((g == want).all() for g in got)
    print("SANITIZED_ASSEMBLE_OK", len(cases))
""")


@pytest.fixture(scope="module")
def sanitized(tmp_path_factory):
    """sanitize flag -> (env that loads that build, or a reason to skip).
    One build a flag for the module's tests."""
    made: dict = {}

    def get(sanitize: str) -> dict:
        if sanitize not in made:
            made[sanitize] = _sanitized_env(tmp_path_factory.mktemp("build"), sanitize)
        env = made[sanitize]
        if isinstance(env, str):
            pytest.skip(env)
        return env

    return get


def _sanitized_env(build_dir, sanitize: str):
    runtime = _runtime_for(sanitize)
    if runtime is None:
        return f"no sanitizer runtime for -fsanitize={sanitize}"
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["XFLOW_NATIVE_SANITIZE"] = sanitize
    env["XFLOW_NATIVE_CACHE"] = str(build_dir)
    # pre-build the sanitized .so WITHOUT the preload: the driver would
    # otherwise spawn g++ with the sanitizer runtime LD_PRELOADed into
    # it, which deadlocks outright on some kernels (observed on 4.4:
    # zero CPU until the timeout). The cache key includes the sanitize
    # flag, so the preloaded driver below picks this build up as-is.
    build = subprocess.run(
        [sys.executable, "-c",
         "from xflow_tpu.data.native import _build_lib; print(_build_lib())"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert build.returncode == 0, f"sanitized build failed:\n{build.stderr}"
    env["LD_PRELOAD"] = runtime
    # leak checking would flag the PYTHON interpreter's own allocations;
    # the parser's handles are close()d explicitly, which IS exercised
    env["ASAN_OPTIONS"] = "detect_leaks=0:halt_on_error=1:exitcode=66"
    env["TSAN_OPTIONS"] = "halt_on_error=1:exitcode=66"
    return env


def _run_driver(tmp_path, env, source: str, ok: str) -> None:
    generate_shards(str(tmp_path / "train"), 1, 700, num_fields=7,
                    ids_per_field=40, seed=3)
    driver = tmp_path / "driver.py"
    driver.write_text(source)
    r = subprocess.run(
        [sys.executable, str(driver), str(tmp_path / "train-00000")],
        capture_output=True, text=True, env=env, timeout=600,
    )
    if r.returncode != 0 and "cannot be preloaded" in (r.stderr or ""):
        pytest.skip(f"sanitizer runtime not preloadable: {env['LD_PRELOAD']}")
    assert r.returncode == 0, (
        f"-fsanitize={env['XFLOW_NATIVE_SANITIZE']} run failed "
        f"(rc={r.returncode})\nstdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    )
    assert ok in r.stdout, r.stdout


@pytest.mark.parametrize("sanitize", ["thread", "address,undefined"])
def test_mt_parser_under_sanitizer(tmp_path, sanitized, sanitize):
    _run_driver(tmp_path, sanitized(sanitize), DRIVER, "SANITIZED_PARITY_OK")


@pytest.mark.parametrize("sanitize", ["thread", "address,undefined"])
def test_assemble_rows_under_sanitizer(tmp_path, sanitized, sanitize):
    _run_driver(tmp_path, sanitized(sanitize), ASSEMBLE_DRIVER, "SANITIZED_ASSEMBLE_OK")
