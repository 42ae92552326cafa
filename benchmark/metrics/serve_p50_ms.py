"""Median of answered - due over every request sent in the window, the
whole path as the generator's clock sees it; a request that failed
counts as slower than any. In an open loop below the knee it is what a
query costs with the queueing of its load; in a closed loop it is the
callers over the requests a second."""

META = {"layer": "whole request", "unit": "ms", "source": "host_clock", "better": "lower"}
MIN_REQUESTS = 100


def read(run: dict):
    w = run.get("window") or {}
    if w.get("requests", 0) < MIN_REQUESTS:
        return None
    return w["p50_ms"]
