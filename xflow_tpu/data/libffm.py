"""libffm-format reader (pure-Python reference path).

Format: ``label\\tfield:feature:value [field:feature:value ...]`` — one
example per line (see `/root/reference/data/small_train-00000`).

Semantics preserved from the reference parser
(`/root/reference/src/io/load_data_from_disk.cc:103-210`):

- the label token is parsed as a float; label = 1 iff > 1e-7
  (`load_data_from_disk.cc:131-134`);
- each feature token contributes ``(fgid, hash(feature_id_string))``;
  the *value* field is never parsed (`:150-153` break after field 1) —
  features are binary;
- the feature id is hashed as a *string* (`:151`); we use the framework
  hash (hashing.fnv1a64) instead of implementation-defined `std::hash`;
- reading is block-buffered with partial-line carry (`:108-124`); the
  Python path just streams lines (the C++ native parser keeps the
  block-buffered design for throughput).

The reference's per-rank shard convention ``"%s-%05d" % (prefix, rank)``
(`lr_worker.cc:210`) is provided by `shard_path`.
"""

from __future__ import annotations

import os
import re
from typing import Iterator, Optional

import numpy as np

from xflow_tpu.hashing import fnv1a64, slot_of
from xflow_tpu.jsonl import JsonlAppender

# re.ASCII: strtod reads ASCII digits and letters only ('١' is no 1,
# 'ı' no i)
_NUM_PREFIX = re.compile(r"^[+-]?(?:\d+\.?\d*|\.\d+)(?:[eE][+-]?\d+)?", re.ASCII)
_HEX_PREFIX = re.compile(
    r"^[+-]?0[xX][0-9a-fA-F]+(?:\.[0-9a-fA-F]*)?(?:[pP][+-]?\d+)?", re.ASCII
)
_INFNAN_PREFIX = re.compile(
    r"^[+-]?(?:infinity|inf|nan(?:\([a-zA-Z0-9_]*\))?)", re.IGNORECASE | re.ASCII
)
# ASCII whitespace only: C code (and strtod) never treats unicode
# whitespace specially, so the Python path must not either
_ASCII_WS = " \t\r\n\v\f"
_TOKEN_SEP = re.compile(r"[ \t\r\v\f]+")


def _strtod(tok: str) -> float:
    """C strtod semantics: parse the longest numeric prefix, 0.0 for junk.

    The native parser uses strtod for labels and field ids
    (`native/parser.cc`); the Python path must parse the same file to the
    same batches (round-1 divergence: `int(float(...))` raised on junk
    fgids while the native path yielded 0 and continued). Covers the
    strtod corners Python's float() handles differently: hex floats
    (C99, float() rejects), underscore digit groups (float() accepts,
    strtod stops at the underscore) and non-ASCII digits and spaces
    (float() reads '١' as 1, strtod stops there)."""
    tok = tok.strip(_ASCII_WS)
    if "_" not in tok and tok.isascii():
        try:
            return float(tok)  # fast path; also covers inf/nan like strtod
        except ValueError:
            pass
    m = _HEX_PREFIX.match(tok)
    if m:
        return float.fromhex(m.group(0))
    m = _INFNAN_PREFIX.match(tok)
    if m:
        # strtod parses 'inf'/'infinity'/'nan(...)' prefixes with junk after
        return float(re.sub(r"\(.*\)", "", m.group(0)))
    m = _NUM_PREFIX.match(tok)
    return float(m.group(0)) if m else 0.0


def _fgid_i32(x: float) -> int:
    """Field id as int32 with explicit nan→0 and saturation — the defined
    semantics both parsers implement (a raw C cast would be UB here)."""
    if x != x:
        return 0
    if x >= 2147483647.0:
        return 2147483647
    if x <= -2147483648.0:
        return -2147483648
    return int(x)


def shard_path(prefix: str, rank: int) -> str:
    """Reference shard naming: `<prefix>-%05d` (`lr_worker.cc:210`)."""
    return "%s-%05d" % (prefix, rank)


def split_line(
    line: str,
) -> Optional[tuple[float, list[int], list[str]]]:
    """The PARSE half of `parse_line`: label + token split, feature id
    strings still unhashed → (label, fields, feature-id strings).

    Split out so the pipeline profiler (telemetry.PipelineProfiler) can
    attribute parse and hash time separately. NOTE: `parse_line` does
    NOT compose these halves — it keeps its own fused single-pass loop
    so the un-profiled hot path pays nothing for the split — so any
    token-rule change must be made in BOTH places; the parity is pinned
    by tests/test_hotpath.py::test_parse_line_matches_profiled_halves
    and the counter/parser parity suite."""
    line = line.strip(_ASCII_WS)
    if not line:
        return None
    parts = line.split("\t", 1)
    if len(parts) == 1:
        # tolerate space-separated label too
        parts = line.split(" ", 1)
        if len(parts) == 1:
            return None
    label = 1.0 if _strtod(parts[0]) > 1e-7 else 0.0
    fields: list[int] = []
    ids: list[str] = []
    for tok in _TOKEN_SEP.split(parts[1]):
        pieces = tok.split(":")
        if len(pieces) < 2:
            continue
        fields.append(_fgid_i32(_strtod(pieces[0])))
        ids.append(pieces[1])
    return label, fields, ids


def hash_ids(ids: list[str], log2_slots: int, salt: int = 0) -> np.ndarray:
    """The HASH half: feature-id strings → folded slot ids (int32)."""
    return np.asarray(
        [slot_of(fnv1a64(t.encode("utf-8"), salt), log2_slots) for t in ids],
        dtype=np.int32,
    )


def parse_line(
    line: str, log2_slots: int, salt: int = 0
) -> Optional[tuple[float, np.ndarray, np.ndarray]]:
    """Parse one libffm line → (label, fields[int32], slots[int32]).

    Deliberately the FUSED single-pass loop (hash inline, no
    intermediate id-string list) — this is the Python fallback parser's
    hot path, and the profiled split through `split_line` + `hash_ids`
    must cost the un-profiled path nothing. The three functions share
    the token rules; parity is pinned by tests/test_libffm.py and the
    counter/parser parity suite."""
    line = line.strip(_ASCII_WS)
    if not line:
        return None
    parts = line.split("\t", 1)
    if len(parts) == 1:
        # tolerate space-separated label too
        parts = line.split(" ", 1)
        if len(parts) == 1:
            return None
    label = 1.0 if _strtod(parts[0]) > 1e-7 else 0.0
    fields = []
    slots = []
    for tok in _TOKEN_SEP.split(parts[1]):
        pieces = tok.split(":")
        if len(pieces) < 2:
            continue
        fields.append(_fgid_i32(_strtod(pieces[0])))
        slots.append(slot_of(fnv1a64(pieces[1].encode("utf-8"), salt), log2_slots))
    return (
        label,
        np.asarray(fields, dtype=np.int32),
        np.asarray(slots, dtype=np.int32),
    )


def iter_examples(
    path: str, log2_slots: int, salt: int = 0, profiler=None
) -> Iterator[tuple[float, np.ndarray, np.ndarray]]:
    """Stream (label, fields, slots) examples from a libffm file.

    `profiler` (telemetry.PipelineProfiler, optional) attributes wall
    time to the read / parse / hash stages; the per-line accumulations
    batch locally and flush to the (locked) profiler every few hundred
    lines so attribution never contends per row. None = the exact
    historical loop."""
    if profiler is not None:
        yield from _profiled_iter_examples(path, log2_slots, salt, profiler)
        return
    with open(path, "r") as f:
        for line in f:
            ex = parse_line(line, log2_slots, salt)
            if ex is not None:
                yield ex


def _profiled_iter_examples(
    path: str, log2_slots: int, salt: int, profiler
) -> Iterator[tuple[float, np.ndarray, np.ndarray]]:
    import time

    pc = time.perf_counter
    acc = {"read": 0.0, "parse": 0.0, "hash": 0.0}
    pending = 0
    try:
        with open(path, "r") as f:
            while True:
                t0 = pc()
                line = f.readline()
                acc["read"] += pc() - t0
                if not line:
                    return
                t0 = pc()
                t = split_line(line)
                acc["parse"] += pc() - t0
                if t is None:
                    continue
                label, fields, ids = t
                t0 = pc()
                slots = hash_ids(ids, log2_slots, salt)
                acc["hash"] += pc() - t0
                pending += 1
                if pending >= 512:
                    profiler.add_many(acc)
                    acc = {"read": 0.0, "parse": 0.0, "hash": 0.0}
                    pending = 0
                yield label, np.asarray(fields, dtype=np.int32), slots
    finally:
        # flush the tail (and the abandonment path: prefetch's close()
        # cascade raises GeneratorExit through the yield above)
        profiler.add_many(acc)


def read_examples(
    path: str, log2_slots: int, salt: int = 0
) -> list[tuple[float, np.ndarray, np.ndarray]]:
    return list(iter_examples(path, log2_slots, salt))


def count_rows(path: str) -> int:
    """Count the examples `iter_examples` would yield, without parsing
    tokens — `parse_line` yields a row iff the stripped line still
    contains a label separator (tab or space)."""
    n = 0
    with open(path, "r") as f:
        for line in f:
            s = line.strip(_ASCII_WS)
            if s and ("\t" in s or " " in s):
                n += 1
    return n


class QuarantineWriter(JsonlAppender):
    """Append-only JSONL sink for bad (feature-less) records
    (data.quarantine_path; docs/ROBUSTNESS.md).

    One line per bad row: source path, batch/row position, label — enough
    to locate the offending region of a shard for offline triage without
    re-parsing the whole file. Lifecycle (lazy open with parent-dir
    creation, flush-per-record, reopen-safe close) AND the ts/rank/run_id
    provenance stamp come from the shared appender (xflow_tpu/jsonl.py),
    so quarantine records join the metrics stream on (run_id, rank, ts).
    Written rows also tick the telemetry registry
    (`data.quarantined_rows`), surfacing in metrics window records."""

    def __init__(self, path: str = ""):
        super().__init__(path)
        self.written = 0

    def write(self, source: str, batch_index: int, row: int, label: float) -> None:
        if not self._path:
            return
        self.append(
            {"source": source, "batch": batch_index, "row": row, "label": label}
        )
        self.written += 1
        from xflow_tpu.telemetry import default_registry

        default_registry().counter("data.quarantined_rows").inc()


def available_shards(prefix: str) -> list[str]:
    """All `<prefix>-NNNNN` shard files that exist, in rank order."""
    out = []
    rank = 0
    while True:
        p = shard_path(prefix, rank)
        if not os.path.exists(p):
            break
        out.append(p)
        rank += 1
    return out
