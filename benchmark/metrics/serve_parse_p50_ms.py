"""Median time the server's front end takes to read a request's rows
(JSON rows -> the padded block the coalescer queues: parser and hash):
the `parse` request spans of the window (a traced run samples every
request)."""

META = {"layer": "front end", "unit": "ms", "source": "program_span", "better": "lower"}
MIN_SPANS = 100


def read(run: dict):
    from lib import serve_stats

    spans = (run.get("serve") or {}).get("spans", {}).get("parse", [])
    if len(spans) < MIN_SPANS:
        return None
    return serve_stats.percentile(sorted(s["dur_ms"] for s in spans), 50.0)
