#!/usr/bin/env python3
"""The serving traffic generator: a traffic file's parameters and a seed
-> a pool of pCTR request bodies -> a closed or an open loop of HTTP
requests over the server's unix socket, from a process of its own.

    python3 benchmark/lib/loadgen.py --spec <spec.json>

It imports no jax and nothing of the program (the chip belongs to the
process that serves). The parent writes the spec (seed, sizes, traffic,
socket, output directory), waits for the `ready` line (the bodies are
made before any window) and then sends one JSON command a line on stdin:
{"cmd": "run", "tag": ..., "seconds": ...} or {"cmd": "quit"}. A run
writes `<out>/<tag>.npz`: for every request when it was due, sent and
answered (this process's clock, seconds from the run's start), its
status, which body of the pool it sent, the generation that answered and
the pCTRs returned; it ends when every request sent has been answered or
has failed. Both loops are `tools/serve_bench.py`'s, copied and cut to
what a cell needs: the closed loop keeps one request in flight on each
connection; the open loop sends on a Poisson schedule from the seed and
times each request from when it was due.

Every seed gives the same multiset of request sizes (the quantiles of
the traffic file's discretised log-normal) and, in the open loop, the
same multiset of gaps between arrivals (the quantiles of the exponential
at the file's rate), each in another order, and ids drawn anew: runs
with different seeds do the same amount of work.
"""

from __future__ import annotations

import http.client
import json
import os
import socket
import statistics
import sys
import threading
import time

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from lib.traffic import _format_rows, draw_rows
else:
    from .traffic import _format_rows, draw_rows

POOL_STREAM = 101  # draw_rows stream of the pool's ids
SOCKET_TIMEOUT_S = 60.0  # a request unanswered this long has failed
_NO_LABELS = {"scale": 1.0, "noise": 1.0, "truth_seed": 0}  # draw_rows draws labels; requests carry none


# ---------------------------------------------------------------- the pool


def request_sizes(spec: dict, n: int) -> np.ndarray:
    """Rows a request, the same `n` values for every seed: quantile
    (i + 1/2) / n of a log-normal (median, sigma), rounded and clipped."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"rows_per_request.dist={spec['dist']!r}: expected lognormal")
    norm = statistics.NormalDist()
    z = np.array([norm.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(float(spec["median"])) + float(spec["sigma"]) * z)
    return np.clip(np.rint(x), int(spec["min"]), int(spec["max"])).astype(np.int64)


def make_pool(seed: int, cfg: dict, traffic: dict) -> dict:
    """The seed's pool: `sizes` [n] rows a request (the fixed multiset,
    ordered by the seed), `offsets` [n + 1] into `ids` [rows, fields]."""
    n = int(traffic["pool_requests"])
    spec = traffic["rows_per_request"]
    sizes = np.random.default_rng([seed, POOL_STREAM]).permutation(request_sizes(spec, n))
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    nf = int(cfg["num_fields"])
    n_ids = max(int((1 << int(cfg["log2_slots"])) * float(traffic["ids"]["candidates_per_slot"])) // nf, 1)
    ids, _, _ = draw_rows(seed, POOL_STREAM, int(offsets[-1]), nf, n_ids,
                          {**traffic, "labels": _NO_LABELS})
    return {"sizes": sizes, "offsets": offsets, "ids": ids}


def make_bodies(pool: dict) -> list:
    """One POST body a request: {"rows": ["f:id:1 f:id:1 ...", ...]},
    libffm feature rows without a label."""
    ids = pool["ids"]
    text = _format_rows(ids, np.zeros(len(ids), np.uint8)).tobytes()
    rows = [line[2:] for line in text.split(b"\n")[:-1]]  # drop the writer's "0\t"
    off = pool["offsets"]
    return [b'{"rows": ["' + b'", "'.join(rows[off[i]:off[i + 1]]) + b'"]}'
            for i in range(len(off) - 1)]


def arrivals(seed: int, rate_rps: float, seconds: float) -> np.ndarray:
    """Arrival times in [0, seconds) of a Poisson process at `rate_rps`:
    its expected count of arrivals, rate x seconds, and between them the
    same gaps for every seed, quantile (i + 1/2) / n of the exponential,
    in the seed's order; scaled so that the last comes half a mean gap
    before the window's end."""
    n = max(int(round(rate_rps * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate_rps
    t = np.cumsum(np.random.default_rng([seed, POOL_STREAM, 1]).permutation(gaps))
    return t * (seconds / (t[-1] + 0.5 / rate_rps))


# ---------------------------------------------------------------- the loops


class UnixHTTPConnection(http.client.HTTPConnection):
    """http.client over an AF_UNIX path."""

    def __init__(self, path: str, timeout: float = SOCKET_TIMEOUT_S):
        super().__init__("localhost", timeout=timeout)
        self._path = path

    def connect(self):
        self.sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self.sock.settimeout(self.timeout)
        self.sock.connect(self._path)


class Connection:
    """One keep-alive connection of the callers' pool, connected before
    it is used (one at a time, as a pool fills: the server's listen
    queue is short); a transport failure reads as status 599 and the
    next request reconnects."""

    def __init__(self, path: str, patience_s: float = 20.0):
        self._path = path
        give_up = time.perf_counter() + patience_s
        while True:
            self._conn = UnixHTTPConnection(path)
            try:
                self._conn.connect()
                return
            except OSError:
                if time.perf_counter() > give_up:
                    raise
                time.sleep(0.01)

    def post(self, body: bytes) -> tuple[int, dict | None]:
        try:
            self._conn.request("POST", "/predict", body, {"Content-Type": "application/json"})
            resp = self._conn.getresponse()
            return resp.status, json.loads(resp.read())
        except Exception:  # noqa: BLE001 - any transport or decode failure fails the request
            self.close()
            self._conn = UnixHTTPConnection(self._path)
            time.sleep(0.01)  # a server that is gone must not make a closed loop spin
            return 599, None

    def close(self) -> None:
        try:
            self._conn.close()
        except Exception:  # noqa: BLE001
            pass


def _one(conn: Connection, bodies: list, sizes, idx: int, due: float, t0: float, log: list, pctr: list) -> None:
    sent = time.perf_counter() - t0
    status, payload = conn.post(bodies[idx])
    done = time.perf_counter() - t0
    p = payload.get("pctr") if status == 200 and isinstance(payload, dict) else None
    if not isinstance(p, list) or len(p) != int(sizes[idx]):
        p, status = [], (status if status != 200 else 598)  # 598: answered, not one pCTR a row
    gen = int(payload.get("generation", -1)) if p else -1
    log.append((idx, due if due >= 0 else sent, sent, done, status, gen, len(p)))
    pctr.extend(p)


def run_loop(conns: list, bodies: list, sizes, traffic: dict, seconds: float, cursors: list, seed: int) -> dict:
    """One window of `seconds`: requests are sent until the window's
    end and every one sent is waited for. `conns` is the callers' pool,
    kept from window to window; `cursors` (one a connection) say where
    in the pool of bodies each connection goes on, and are advanced."""
    n_conn, n_pool = int(traffic["connections"]), len(bodies)
    open_loop = traffic["loop"] == "open"
    if traffic["loop"] not in ("open", "closed"):
        raise ValueError(f"loop={traffic['loop']!r}: expected closed|open")
    due_at = arrivals(seed, float(traffic["rate_rps"]), seconds) if open_loop else None
    taken = [0]
    lock = threading.Lock()
    logs = [[] for _ in range(n_conn)]
    pctrs = [[] for _ in range(n_conn)]
    t0 = time.perf_counter() + 0.05  # every thread is up before the first send

    def closed(c: int) -> None:
        while time.perf_counter() - t0 < seconds:
            _one(conns[c], bodies, sizes, cursors[c] % n_pool, -1.0, t0, logs[c], pctrs[c])
            cursors[c] += 1

    def opened(c: int) -> None:
        while True:
            with lock:
                k = taken[0]
                taken[0] += 1
            if k >= len(due_at):
                return
            wait = due_at[k] - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(wait)
            _one(conns[c], bodies, sizes, (cursors[0] + k) % n_pool, float(due_at[k]), t0, logs[c], pctrs[c])

    threads = [threading.Thread(target=opened if open_loop else closed, args=(c,), daemon=True)
               for c in range(n_conn)]
    time.sleep(max(t0 - time.perf_counter(), 0.0))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    closed_s = time.perf_counter() - t0
    if open_loop:
        cursors[0] += len(due_at)
    log = np.array([r for part in logs for r in part], np.float64).reshape(-1, 7)
    return {
        "pool_index": log[:, 0].astype(np.int64), "due_s": log[:, 1], "sent_s": log[:, 2],
        "done_s": log[:, 3], "status": log[:, 4].astype(np.int64), "generation": log[:, 5].astype(np.int64),
        "n_pctr": log[:, 6].astype(np.int64),
        "pctr": np.array([x for part in pctrs for x in part], np.float64),
        "seconds": np.float64(seconds), "closed_s": np.float64(closed_s),
        "offered": np.int64(len(due_at) if open_loop else len(log)),
    }


# ---------------------------------------------------------------- the child


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True)
    args = ap.parse_args(argv)
    with open(args.spec) as f:
        spec = json.load(f)
    t = time.perf_counter()
    pool = make_pool(int(spec["seed"]), spec["cfg"], spec["traffic"])
    bodies = make_bodies(pool)
    n_conn = int(spec["traffic"]["connections"])
    # each connection walks the pool in order from its own offset
    cursors = [c * (len(bodies) // n_conn) for c in range(n_conn)]
    print(json.dumps({"ready": True, "bodies": len(bodies), "rows": int(pool["offsets"][-1]),
                      "body_bytes": sum(map(len, bodies)), "build_s": time.perf_counter() - t}), flush=True)
    conns: list = []
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "quit":
            break
        traffic = {**spec["traffic"], **cmd.get("traffic", {})}  # a hand-run sweep changes the loop, not the pool
        n = int(traffic["connections"])
        cursors += [c * (len(bodies) // n) for c in range(len(cursors), n)]
        conns += [Connection(spec["socket"]) for _ in range(len(conns), n)]  # the server is up by the first run
        out = run_loop(conns, bodies, pool["sizes"], traffic, float(cmd["seconds"]), cursors, int(spec["seed"]))
        path = os.path.join(spec["out"], cmd["tag"] + ".npz")
        np.savez(path, **out)
        print(json.dumps({"done": cmd["tag"], "requests": int(len(out["status"])), "path": path}), flush=True)
    for c in conns:
        c.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
