"""95th percentile of answered - due over every request sent in the
window, the whole path as the generator's clock sees it; a request that
failed counts as slower than any."""

META = {"layer": "whole request", "unit": "ms", "source": "host_clock", "better": "lower"}
MIN_REQUESTS = 200  # ten samples beyond the percentile


def read(run: dict):
    w = run.get("window") or {}
    if w.get("requests", 0) < MIN_REQUESTS:
        return None
    return w["p95_ms"]
