"""Median time of a predict call as the server's worker thread sees it
(batch assembled -> pCTRs on the host): the window's `device_batch`
spans, one a device batch."""

import statistics

META = {"layer": "predict program", "unit": "ms", "source": "program_span", "better": "lower"}


def read(run: dict):
    spans = (run.get("serve") or {}).get("spans", {}).get("device_batch", [])
    if not spans:
        return None
    return statistics.median(s["dur_ms"] for s in spans)
