"""From the generator's log of a window to the serving numbers: the
rate, the tail, what failed, and the comparison of every answer with
the plain reference.

A log is what `lib/loadgen.py run_loop` returns: one entry a request
sent in the window (`due_s`, `sent_s`, `done_s` on the generator's
clock from the window's start, `status`, `pool_index`, `generation`,
`n_pctr`) and `pctr`, the answers in the same order.
"""

from __future__ import annotations

import math

import numpy as np

FAILED_MS = 60000.0  # a request that failed counts as slower than any: the generator's time-out, or the slowest answered


def percentile(sorted_values: np.ndarray, q: float) -> float:
    """Nearest rank: the smallest value with at least q% of the sample
    at or below it."""
    n = len(sorted_values)
    return float(sorted_values[min(max(math.ceil(q / 100.0 * n) - 1, 0), n - 1)])


def window_stats(log: dict) -> dict:
    seconds = float(log["seconds"])
    ok = log["status"] == 200
    inside = ok & (log["done_s"] <= seconds)
    rows_in_window = int(log["n_pctr"][inside].sum())
    lat = (log["done_s"] - log["due_s"]) * 1e3
    slowest = max(FAILED_MS, float(lat[ok].max()) if ok.any() else 0.0)
    lat = np.sort(np.where(ok, lat, slowest))
    sent_late = (log["sent_s"] - log["due_s"]) * 1e3
    return {
        "window_s": seconds,
        "closed_s": float(log["closed_s"]),  # when the last request sent in the window was answered
        "requests": int(len(ok)),
        "answered": int(ok.sum()),
        "failed": int((~ok).sum()),
        "shed": int((log["status"] == 503).sum()),
        "statuses": {str(int(k)): int(v) for k, v in zip(*np.unique(log["status"], return_counts=True))},
        "rows_answered": int(log["n_pctr"][ok].sum()),
        "rows_in_window": rows_in_window,
        "requests_in_window": int(inside.sum()),
        "rows_per_s": rows_in_window / seconds,
        "requests_per_s": float(inside.sum()) / seconds,
        "p50_ms": percentile(lat, 50.0) if len(lat) else float("nan"),
        "p95_ms": percentile(lat, 95.0) if len(lat) else float("nan"),
        "p99_ms": percentile(lat, 99.0) if len(lat) else float("nan"),
        "max_ms": float(lat[-1]) if len(lat) else float("nan"),
        "generator_late_p99_ms": percentile(np.sort(sent_late), 99.0) if len(lat) else float("nan"),
        "generations_extra": max(len(set(log["generation"][ok].tolist())) - 1, 0),
        "offered": int(log["offered"]),
    }


def answered_rows(log: dict, pool: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """-> (entries, row_of_answer, offsets): `entries`, the pool's
    requests that were answered, each once, in order; for every answered
    row its index among the rows of `entries` laid end to end; and the
    `offsets` [len(entries) + 1] of those rows. The answers themselves
    are `log["pctr"]`, one a row of the answered requests in log order."""
    ok = log["status"] == 200
    idx = log["pool_index"][ok]
    entries = np.unique(idx)
    sizes = pool["sizes"][entries]
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    start = offsets[np.searchsorted(entries, idx)]
    n = pool["sizes"][idx]
    first = np.concatenate([[0], np.cumsum(n)])[:-1]
    row_of_answer = np.repeat(start - first, n) + np.arange(int(n.sum()))
    return entries, row_of_answer, offsets


def entry_ids(pool: dict, entries: np.ndarray) -> np.ndarray:
    """The feature ids of `entries`' rows, laid end to end."""
    off = pool["offsets"]
    return np.concatenate([pool["ids"][off[e]:off[e + 1]] for e in entries]) if len(entries) else pool["ids"][:0]


def pctr_gaps(served: np.ndarray, reference: np.ndarray) -> dict:
    """The numbers `correct` compares of the answers: the widest and the
    mean |served - reference| over all rows answered. No answer at all
    reads as far as a pCTR can be, 1."""
    if len(served) == 0 or len(served) != len(reference):
        return {"pctr_max_gap": 1.0, "pctr_mean_gap": 1.0}
    gap = np.abs(np.asarray(served, np.float64) - np.asarray(reference, np.float64))
    return {"pctr_max_gap": float(gap.max()), "pctr_mean_gap": float(gap.mean())}
