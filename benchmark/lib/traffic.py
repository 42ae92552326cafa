"""The one general traffic generator: a traffic file's parameters and a
seed -> rows (ids, labels) -> libffm text shards on disk.

Nothing here imports the program. The id -> slot rule (`slots_of_ids`)
is the benchmark's own copy of the rule the program documents (salted
FNV-1a 64 over the id's decimal string, a multiply/xor-shift fold, a
mask): the reference hashes with this copy, the program with its own
parser, and `correct` compares the two ends.
"""

from __future__ import annotations

import json
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(0x100000001B3)
_FOLD_MUL = np.uint64(0xD6E8FEB86659FD93)
_ID_DIGITS = 10  # feature ids fit 32 bits


def load_traffic(root: str, name: str) -> dict:
    with open(os.path.join(root, "traffic", name + ".json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- rows


def _fmix32(x: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finalizer over a uint32 array (wraps)."""
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def seed_words(seed: int) -> tuple[int, int]:
    """Two 32-bit stream constants from a seed of any size."""
    lo = (seed ^ (seed >> 32) ^ 0x9E3779B9) & 0xFFFFFFFF
    s1 = int(_fmix32(np.array([lo], np.uint32))[0])
    s2 = int(_fmix32(np.array([(s1 + 0x7F4A7C15) & 0xFFFFFFFF], np.uint32))[0])
    return s1, s2


def draw_ranks(rng: np.random.Generator, shape, ids: dict, n_ids: int) -> np.ndarray:
    """Per-field popularity ranks in [0, n_ids): `powerlaw` is the
    inverse CDF of a continuous power law x**-alpha on [1, n_ids+1),
    floored (P(rank r) ~ r**-alpha, the head a little lighter than
    discrete Zipf); `uniform` is uniform."""
    u = rng.random(shape)
    if ids["dist"] == "uniform":
        return np.minimum((u * n_ids).astype(np.int64), n_ids - 1)
    if ids["dist"] != "powerlaw":
        raise ValueError(f"ids.dist={ids['dist']!r}: expected powerlaw|uniform")
    a = 1.0 - float(ids["alpha"])
    top = float(n_ids + 1) ** a
    x = (1.0 + u * (top - 1.0)) ** (1.0 / a)
    return np.clip(x.astype(np.int64) - 1, 0, n_ids - 1)


def _draw_chunk(seed: int, stream: int, chunk: int, rows: int, num_fields: int,
                n_ids: int, traffic: dict) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng([seed, stream, chunk])
    ranks = draw_ranks(rng, (rows, num_fields), traffic["ids"], n_ids)
    ids = (ranks * num_fields + np.arange(num_fields, dtype=np.int64)).astype(np.uint32)
    lab = traffic["labels"]
    # the planted concept is a hashed weight per feature id: no table of
    # n_ids * num_fields truths is ever held
    s1, _ = seed_words(int(lab.get("truth_seed", 0)))
    h = _fmix32(ids ^ np.uint32(s1))
    truth = (h >> np.uint32(8)).astype(np.float32) * np.float32(2.0 / (1 << 24)) - np.float32(1.0)
    logit = truth.sum(axis=1, dtype=np.float64) * float(lab["scale"])
    logit += rng.normal(0.0, float(lab["noise"]), size=rows)
    return ids, (logit > 0.0).astype(np.uint8)


CHUNK_ROWS = 16384


def draw_rows(seed: int, stream: int, rows: int, num_fields: int, n_ids: int,
              traffic: dict, path: str | None = None, threads: int = 8):
    """`rows` examples with one feature in each field, drawn (and, with
    `path`, written as a libffm text shard) in chunks on a few threads:
    global feature ids uint32 [rows, num_fields] (rank * num_fields +
    field, so hot ids are short strings), planted-truth labels uint8
    [rows], and the bytes written."""
    if n_ids * num_fields >= 1 << 32:
        raise ValueError("feature ids must fit 32 bits")
    ids = np.empty((rows, num_fields), np.uint32)
    labels = np.empty(rows, np.uint8)
    spans = [(c, i, min(i + CHUNK_ROWS, rows)) for c, i in enumerate(range(0, rows, CHUNK_ROWS))]

    def work(span):
        c, lo, hi = span
        ids[lo:hi], labels[lo:hi] = _draw_chunk(seed, stream, c, hi - lo, num_fields, n_ids, traffic)
        return _format_rows(ids[lo:hi], labels[lo:hi]) if path else None

    written = 0
    with ThreadPoolExecutor(max_workers=threads) as pool:
        if path is None:
            list(pool.map(work, spans))
        else:
            with open(path, "wb") as f:
                for part in pool.map(work, spans):
                    f.write(part.tobytes())
                    written += part.size
    return ids, labels, written


# ---------------------------------------------------------------- text


def _format_rows(ids: np.ndarray, labels: np.ndarray) -> np.ndarray:
    """[rows, F] ids + labels -> the shard's bytes: `L\\tF:ID:1 F:ID:1 ...\\n`
    (libffm: field:feature:value). Written at a fixed width with NULs
    for absent leading digits, then the NULs are dropped."""
    rows, nf = ids.shape
    fw = len(str(nf - 1))
    tok = fw + 1 + _ID_DIGITS + 3  # field ':' id ':1' sep
    buf = np.zeros((rows, 2 + nf * tok), np.uint8)
    buf[:, 0] = labels + ord("0")
    buf[:, 1] = ord("\t")
    body = buf[:, 2:].reshape(rows, nf, tok)
    for f in range(nf):
        s = str(f).encode()
        body[:, f, fw - len(s):fw] = np.frombuffer(s, np.uint8)
    body[:, :, fw] = ord(":")
    v = ids.astype(np.uint32)
    for d in range(_ID_DIGITS):
        col = fw + _ID_DIGITS - d
        q = v // np.uint32(10)
        digit = (v - q * np.uint32(10)).astype(np.uint8)
        body[:, :, col] = np.where((v > 0) | (d == 0), digit + np.uint8(ord("0")), np.uint8(0))
        v = q
        if d and not v.any():
            break
    body[:, :, fw + 1 + _ID_DIGITS] = ord(":")
    body[:, :, fw + 2 + _ID_DIGITS] = ord("1")
    body[:, :, fw + 3 + _ID_DIGITS] = ord(" ")
    body[:, nf - 1, tok - 1] = ord("\n")
    flat = buf.reshape(-1)
    return flat[flat != 0]


# ---------------------------------------------------------------- hash


def slots_of_ids(ids: np.ndarray, log2_slots: int, salt: int = 0) -> np.ndarray:
    """Feature ids -> table slots, as the text says them: FNV-1a 64 over
    the id's decimal string, folded and masked."""
    v = np.asarray(ids, np.uint64).reshape(-1)
    ndig = np.ones(v.shape, np.int64)
    for k in range(1, _ID_DIGITS):
        ndig += v >= np.uint64(10 ** k)
    h = np.full(v.shape, _FNV_OFFSET ^ np.uint64(salt), np.uint64)
    with np.errstate(over="ignore"):
        for pos in range(_ID_DIGITS - 1, -1, -1):  # most significant first
            live = ndig > pos
            digit = (v // np.uint64(10 ** pos)) % np.uint64(10)
            hn = (h ^ (digit + np.uint64(ord("0")))) * _FNV_PRIME
            h = np.where(live, hn, h)
        x = h ^ (h >> np.uint64(32))
        x = x * _FOLD_MUL
        x = x ^ (x >> np.uint64(32))
    out = (x & np.uint64((1 << log2_slots) - 1)).astype(np.int64)
    return out.reshape(np.shape(ids))


# ---------------------------------------------------------------- a run's data


def make_run_data(workdir: str, seed: int, cfg: dict, traffic: dict, window: bool = True) -> dict:
    """Everything one run reads: `first_steps` one-batch shards (rows
    that all differ, kept in memory for the reference too) and the
    window's shard of `steps_per_pass` batches."""
    B, nf = int(cfg["batch_size"]), int(cfg["num_fields"])
    n_ids = max(int((1 << int(cfg["log2_slots"])) * float(traffic["ids"]["candidates_per_slot"])) // nf, 1)
    os.makedirs(workdir, exist_ok=True)
    first = []
    nbytes = 0
    for k in range(int(traffic["first_steps"])):
        path = os.path.join(workdir, f"first{k + 1}-00000")
        ids, labels, n = draw_rows(seed, 1 + k, B, nf, n_ids, traffic, path)
        nbytes += n
        first.append({"path": path, "ids": ids, "labels": labels})
    steps = int(traffic["steps_per_pass"]) if window else 0
    prefix = os.path.join(workdir, "train")
    ids, _, n = draw_rows(seed, 0, steps * B, nf, n_ids, traffic, prefix + "-00000")
    nbytes += n
    return {
        "first": first, "train_prefix": prefix, "train_ids": ids,
        "rows_per_pass": steps * B, "steps_per_pass": steps,
        "ids_per_field": n_ids, "text_bytes": nbytes,
    }
