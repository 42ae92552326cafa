"""99th percentile of the time a request's rows waited in the coalescer
for their device batch: the `queue` request spans of the window (a
traced run samples every request)."""

META = {"layer": "coalescer", "unit": "ms", "source": "program_span", "better": "lower"}
MIN_SPANS = 100


def read(run: dict):
    from lib import serve_stats

    spans = (run.get("serve") or {}).get("spans", {}).get("queue", [])
    if len(spans) < MIN_SPANS:
        return None
    return serve_stats.percentile(sorted(s["dur_ms"] for s in spans), 99.0)
