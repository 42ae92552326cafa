"""99th percentile of answered - due over every request sent in the
window, the whole path as the generator's clock sees it; a request that
failed counts as slower than any. The percentile MLPerf's server
scenario judges; with some hundreds of queries a window a handful lie
beyond it, so it swings from run to run and takes no bound
(PERF.md section 2)."""

META = {"layer": "whole request", "unit": "ms", "source": "host_clock", "better": "lower"}
MIN_REQUESTS = 300  # three samples beyond the percentile


def read(run: dict):
    w = run.get("window") or {}
    if w.get("requests", 0) < MIN_REQUESTS:
        return None
    return w["p99_ms"]
