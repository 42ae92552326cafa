"""ctypes bindings for the C++ data plane (native/parser.cc).

The shared library is compiled on demand with g++ (no pybind11 in the
image; plain C ABI + ctypes keeps the binding dependency-free) and
cached next to the source keyed by a source hash. `batch_iterator`
prefers this path automatically (DataConfig.use_native_parser) and
falls back to the pure-Python parser if the toolchain is missing.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Iterator

import numpy as np

from xflow_tpu.config import DataConfig
from xflow_tpu.data.schema import SparseBatch

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native", "parser.cc")
_LIB = None
_GIL_LIB = None


def _build_lib() -> str:
    # XFLOW_NATIVE_SANITIZE=thread|address,undefined|… rebuilds the data
    # plane under the named sanitizer(s) — the MT parser is the one
    # concurrent C++ component (SURVEY.md §5 "race detection" plan;
    # tests/test_native_sanitizers.py runs the MT parity check under
    # TSan and ASan+UBSan). The flag value joins the cache key so
    # sanitized and plain builds never collide; the host process must
    # LD_PRELOAD the matching runtime before loading a sanitized .so.
    sanitize = os.environ.get("XFLOW_NATIVE_SANITIZE", "")
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(
            f.read() + sanitize.encode()
        ).hexdigest()[:16]
    cache_dir = os.environ.get(
        "XFLOW_NATIVE_CACHE",
        os.path.join(os.path.dirname(_SRC), "_build"),
    )
    os.makedirs(cache_dir, exist_ok=True)
    so_path = os.path.join(cache_dir, f"libxfparser_{digest}.so")
    if os.path.exists(so_path):
        return so_path
    tmp = tempfile.mktemp(suffix=".so", dir=cache_dir)
    cmd = ["g++", "-O3", "-std=c++17", "-pthread", "-shared", "-fPIC", "-o", tmp, _SRC]
    if sanitize:
        cmd[1:1] = [f"-fsanitize={sanitize}", "-g", "-fno-omit-frame-pointer"]
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, so_path)  # atomic: concurrent builders race benignly
    return so_path


def get_lib() -> ctypes.CDLL:
    global _LIB
    if _LIB is None:
        lib = ctypes.CDLL(_build_lib())
        lib.xf_hash64.restype = ctypes.c_uint64
        lib.xf_hash64.argtypes = [ctypes.c_char_p, ctypes.c_long, ctypes.c_uint64]
        lib.xf_slot.restype = ctypes.c_uint64
        lib.xf_slot.argtypes = [ctypes.c_uint64, ctypes.c_int]
        lib.xf_parser_open.restype = ctypes.c_void_p
        lib.xf_parser_open.argtypes = [ctypes.c_char_p, ctypes.c_long]
        lib.xf_parser_next_batch.restype = ctypes.c_long
        lib.xf_parser_next_batch.argtypes = [
            ctypes.c_void_p,
            ctypes.c_long,
            ctypes.c_long,
            ctypes.c_int,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float),
        ]
        lib.xf_parser_truncated.restype = ctypes.c_long
        lib.xf_parser_truncated.argtypes = [ctypes.c_void_p]
        lib.xf_parser_close.restype = None
        lib.xf_parser_close.argtypes = [ctypes.c_void_p]
        lib.xf_parse_rows.restype = ctypes.c_long
        lib.xf_parse_rows.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_long,
            ctypes.c_long,
            ctypes.c_int,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int32),
        ]
        lib.xf_count_rows.restype = ctypes.c_long
        lib.xf_count_rows.argtypes = [ctypes.c_char_p, ctypes.c_long]
        lib.xf_mt_open.restype = ctypes.c_void_p
        lib.xf_mt_open.argtypes = [
            ctypes.c_char_p,
            ctypes.c_long,
            ctypes.c_int,
            ctypes.c_long,
            ctypes.c_int,
            ctypes.c_uint64,
        ]
        lib.xf_mt_next_batch.restype = ctypes.c_long
        lib.xf_mt_next_batch.argtypes = [
            ctypes.c_void_p,
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float),
        ]
        lib.xf_mt_truncated.restype = ctypes.c_long
        lib.xf_mt_truncated.argtypes = [ctypes.c_void_p]
        lib.xf_mt_close.restype = None
        lib.xf_mt_close.argtypes = [ctypes.c_void_p]
        lib.xf_plan_sorted.restype = ctypes.c_long
        lib.xf_plan_sorted.argtypes = [
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_long,
            ctypes.c_long,
            ctypes.c_long,
            ctypes.c_long,
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
        ]
        lib.xf_plan_sorted_wire.restype = ctypes.c_long
        lib.xf_plan_sorted_wire.argtypes = [
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_long,
            ctypes.c_long,
            ctypes.c_long,
            ctypes.c_long,
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint16),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int32),
        ]
        _LIB = lib
    return _LIB


def _plan_sorted_call(slots, mask, fields, num_slots: int, window: int,
                      np_len: int, wire: bool):
    """Shared marshalling for the two C plan emitters — ONE place for
    the size validation and pointer plumbing; only the output dtypes
    and entry point differ."""
    lib = get_lib()
    slots = np.ascontiguousarray(slots, np.int32)
    mask_flat = np.ascontiguousarray(mask, np.float32).ravel()
    B, F = slots.shape
    n = B * F
    # C reads n entries from each buffer: a size mismatch that would be a
    # loud IndexError in the numpy path must not become an OOB heap read
    if mask_flat.size != n:
        raise ValueError(f"mask size {mask_flat.size} != slots size {n}")
    if fields is not None and np.asarray(fields).size != n:
        raise ValueError(f"fields size {np.asarray(fields).size} != slots size {n}")
    row_dt, mask_dt, f_dt = (
        (np.uint16, np.uint8, np.uint8) if wire else (np.int32, np.float32, np.int32)
    )
    out_slots = np.empty(np_len, np.int32)
    out_row = np.empty(np_len, row_dt)
    out_mask = np.empty(np_len, mask_dt)
    out_fields = np.empty(np_len, f_dt) if fields is not None else None
    win_off = np.empty(num_slots // window + 1, np.int32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    f32p = ctypes.POINTER(ctypes.c_float)
    rowp = ctypes.POINTER(ctypes.c_uint16 if wire else ctypes.c_int32)
    maskp = ctypes.POINTER(ctypes.c_uint8 if wire else ctypes.c_float)
    fp = ctypes.POINTER(ctypes.c_uint8 if wire else ctypes.c_int32)
    fields_c = (
        np.ascontiguousarray(fields, np.int32).ctypes.data_as(i32p)
        if fields is not None
        else None
    )
    fn = lib.xf_plan_sorted_wire if wire else lib.xf_plan_sorted
    rc = fn(
        slots.ctypes.data_as(i32p),
        mask_flat.ctypes.data_as(f32p),
        fields_c,
        n,
        F,
        num_slots,
        window,
        np_len,
        out_slots.ctypes.data_as(i32p),
        out_row.ctypes.data_as(rowp),
        out_mask.ctypes.data_as(maskp),
        out_fields.ctypes.data_as(fp) if out_fields is not None else None,
        win_off.ctypes.data_as(i32p),
    )
    if rc == -2:
        raise ValueError(
            "xf_plan_sorted_wire: data violated the wire contract "
            "(row ≥ 2^16, field ≥ 2^8, or a non-0/1 mask) — the caller's "
            "config-derived bounds disagree with the batch"
        )
    if rc != 0:
        raise ValueError(
            f"{'xf_plan_sorted_wire' if wire else 'xf_plan_sorted'} "
            f"failed (rc={rc})"
        )
    return out_slots, out_row, out_mask, out_fields, win_off


def native_plan_sorted(slots, mask, fields, num_slots: int, window: int, np_len: int):
    """C radix-sort plan builder (xf_plan_sorted). Returns the plan
    arrays (sorted_slots, sorted_row, sorted_mask, sorted_fields|None,
    win_off) or raises on toolchain/library failure. ctypes releases the
    GIL during the call, so stacked sub-batch plans can run in parallel
    host threads."""
    return _plan_sorted_call(slots, mask, fields, num_slots, window, np_len,
                             wire=False)


def native_plan_sorted_wire(slots, mask, fields, num_slots: int, window: int,
                            np_len: int):
    """C radix-sort plan builder emitting WIRE dtypes directly
    (xf_plan_sorted_wire): uint16 rows, uint8 mask/fields — the
    compact_plan_wire numpy passes never run. Callers must have
    checked the CONFIG bounds (rows ≤ 2^16, fields < 2^8); rc=-2
    means a bound or the 0/1-mask contract was violated by the data —
    a pipeline bug, raised loudly."""
    return _plan_sorted_call(slots, mask, fields, num_slots, window, np_len,
                             wire=True)


def native_count_rows(path: str, block_bytes: int) -> int:
    """Rows the native parser would produce for `path` (same predicate,
    no token parsing); raises on missing file / read error."""
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    n = int(get_lib().xf_count_rows(path.encode(), block_bytes))
    if n < 0:
        raise OSError(f"xf_count_rows failed for {path}")
    return n


def native_parse_rows(buf: bytes, offsets: np.ndarray, cfg: DataConfig):
    """Parse rows laid end to end in `buf` (row i is
    buf[offsets[i]:offsets[i + 1]]) into padded blocks (xf_parse_rows).
    Returns (rc, slots, fields, mask, nnz): slots/fields int32 and mask
    float32 [n, max_nnz], nnz int32 [n] (parseable tokens a row before
    truncation); rc is n when every row parsed, else the row it stopped
    at (see parser.cc). ctypes releases the GIL for the call, so request
    threads parse in parallel."""
    lib = get_lib()
    offsets = np.ascontiguousarray(offsets, np.int64)
    n, k = offsets.size - 1, cfg.max_nnz
    if n < 0 or (n and (offsets[0] < 0 or offsets[-1] > len(buf)
                        or np.any(np.diff(offsets) < 0))):
        raise ValueError(f"offsets do not lie within the {len(buf)}-byte buffer")
    slots = np.zeros((n, k), np.int32)
    fields = np.zeros((n, k), np.int32)
    mask = np.zeros((n, k), np.float32)
    nnz = np.zeros((n,), np.int32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    rc = lib.xf_parse_rows(
        buf,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n,
        k,
        cfg.log2_slots,
        cfg.hash_salt,
        slots.ctypes.data_as(i32p),
        fields.ctypes.data_as(i32p),
        mask.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        nnz.ctypes.data_as(i32p),
    )
    return int(rc), slots, fields, mask, nnz


def get_gil_lib() -> ctypes.PyDLL:
    """The same library through a handle whose calls KEEP the GIL, for
    `native_assemble_rows` alone.

    A CDLL call releases the GIL and takes it back on return. A serving
    worker that hands it off while request threads wait for it needs
    ~0.5 ms to win it back under the benchmark's 16 callers, each time;
    the copies of a whole packed batch (~0.4 MB) take tens of µs with
    the GIL held. So the call that only copies keeps it, and the worker
    pays no hand-off for assembly."""
    global _GIL_LIB
    if _GIL_LIB is None:
        lib = ctypes.PyDLL(_build_lib())
        lib.xf_assemble_rows.restype = ctypes.c_long
        lib.xf_assemble_rows.argtypes = [
            ctypes.c_void_p,
            ctypes.c_long,
            ctypes.c_long,
            ctypes.c_long,
            ctypes.c_void_p,
        ]
        _GIL_LIB = lib
    return _GIL_LIB


def native_assemble_rows(dst: np.ndarray, rung: int, max_nnz: int, blocks: list) -> int:
    """Fill `dst`, int32 [3 * rung * max_nnz + 1], with one packed
    serving batch (xf_assemble_rows): the slots, fields and mask planes
    of `blocks` — (slots, fields, mask) C-contiguous [n, max_nnz], int32
    / int32 / float32, laid end to end — the padding rows zeroed, and
    the count of real rows last. Returns that count."""
    if dst.dtype != np.int32 or dst.size != 3 * rung * max_nnz + 1 or not dst.flags.c_contiguous:
        raise ValueError(f"dst must be C-contiguous int32 [{3 * rung * max_nnz + 1}]")
    desc = []
    for arrays in blocks:
        for a, dtype in zip(arrays, (np.int32, np.int32, np.float32)):
            if (a.dtype != dtype or a.shape != arrays[0].shape or a.shape[1:] != (max_nnz,)
                    or not a.flags.c_contiguous):
                raise ValueError(f"a block plane must be C-contiguous {np.dtype(dtype)} [n, {max_nnz}]")
            desc.append(a.ctypes.data)
        desc.append(arrays[0].shape[0])
    rec = np.array(desc, np.int64)
    rc = get_gil_lib().xf_assemble_rows(dst.ctypes.data, rung, max_nnz, len(blocks), rec.ctypes.data)
    if rc < 0:
        raise ValueError(f"{sum(b[0].shape[0] for b in blocks)} rows do not fit a rung of {rung}")
    return int(rc)


def native_hash(token: bytes, salt: int = 0) -> int:
    return int(get_lib().xf_hash64(token, len(token), salt))


def native_slot(key: int, log2_slots: int) -> int:
    return int(get_lib().xf_slot(key, log2_slots))


class _NativeBatchStream:
    """Eagerly-opened batch stream (construction fails fast on a missing
    file/toolchain, so batch_iterator's guarded construction works).

    `threads=1` uses the sequential block-buffered parser; any other value
    opens the multi-threaded parser pool (N workers over newline-aligned
    file blocks, reassembled in file order — byte-identical output, the
    hashing/strtod cost parallelized; reference analog: the worker thread
    pool `thread_pool.h:70-86`). 0 = auto (hardware concurrency)."""

    def __init__(self, path: str, cfg: DataConfig, batch_size: int):
        self.lib = get_lib()
        if not os.path.exists(path):
            raise FileNotFoundError(path)
        resolved = cfg.parser_threads if cfg.parser_threads > 0 else (os.cpu_count() or 1)
        self.mt = resolved > 1  # 1 available core: sequential parser wins
        if self.mt:
            self.handle = self.lib.xf_mt_open(
                path.encode(), cfg.block_bytes, cfg.parser_threads,
                cfg.max_nnz, cfg.log2_slots, cfg.hash_salt,
            )
        else:
            self.handle = self.lib.xf_parser_open(path.encode(), cfg.block_bytes)
        if not self.handle:
            raise OSError(f"native parser open failed for {path}")
        self.cfg = cfg
        self.batch_size = batch_size
        self.closed = False
        self.started = False
        self.truncated = 0

    def __iter__(self) -> Iterator[SparseBatch]:
        # single-shot stream: re-iterating would call into the freed C handle
        if self.started or self.closed:
            raise RuntimeError("native batch stream is single-use; re-open the file")
        self.started = True
        return self._generate()

    def _generate(self) -> Iterator[SparseBatch]:
        cfg, B, F = self.cfg, self.batch_size, self.cfg.max_nnz
        i32p = ctypes.POINTER(ctypes.c_int32)
        f32p = ctypes.POINTER(ctypes.c_float)
        try:
            while True:
                slots = np.zeros((B, F), np.int32)
                fields = np.zeros((B, F), np.int32)
                mask = np.zeros((B, F), np.float32)
                labels = np.zeros((B,), np.float32)
                row_mask = np.zeros((B,), np.float32)
                if self.mt:
                    n = self.lib.xf_mt_next_batch(
                        self.handle,
                        B,
                        slots.ctypes.data_as(i32p),
                        fields.ctypes.data_as(i32p),
                        mask.ctypes.data_as(f32p),
                        labels.ctypes.data_as(f32p),
                        row_mask.ctypes.data_as(f32p),
                    )
                else:
                    n = self.lib.xf_parser_next_batch(
                        self.handle,
                        B,
                        F,
                        cfg.log2_slots,
                        cfg.hash_salt,
                        slots.ctypes.data_as(i32p),
                        fields.ctypes.data_as(i32p),
                        mask.ctypes.data_as(f32p),
                        labels.ctypes.data_as(f32p),
                        row_mask.ctypes.data_as(f32p),
                    )
                if n < 0:
                    raise OSError(f"native parser I/O error reading batches (ferror)")
                if n == 0:
                    return
                if n < B and cfg.drop_remainder:
                    return
                yield SparseBatch(slots, fields, mask, labels, row_mask)
                if n < B:
                    return
        finally:
            self.close()

    def close(self) -> None:
        if not self.closed:
            if self.mt:
                self.truncated = int(self.lib.xf_mt_truncated(self.handle))
                self.lib.xf_mt_close(self.handle)
            else:
                self.truncated = int(self.lib.xf_parser_truncated(self.handle))
                self.lib.xf_parser_close(self.handle)
            self.closed = True
            if self.truncated:
                import sys

                print(
                    f"xflow: warning: {self.truncated} feature occurrence(s) "
                    f"truncated by data.max_nnz={self.cfg.max_nnz}",
                    file=sys.stderr,
                )


def native_batch_iterator(path: str, cfg: DataConfig, batch_size: int):
    return iter(_NativeBatchStream(path, cfg, batch_size))
