"""Share of the window's step time the prefetch thread spent making
batches: the step records' `host` sums of the producer's work stages
(native parse, plan, cache read and the Python parser's; not the time it
waited in the queue's put) over their step intervals. What is left of
100 is its slack before the host sets the pace."""

META = {"layer": "input pipeline", "unit": "%", "source": "program_span", "better": "lower"}

# the program's telemetry._PIPELINE_HOST_STAGES, as the records name them
WORK = ("read_ms", "parse_ms", "hash_ms", "batch_ms", "pad_ms", "cache_read_ms", "plan_ms")


def read(run: dict):
    recs = run["records"]
    hosts = [r["host"] for r in recs if "host" in r]
    total = sum(r["step_time_p50_ms"] for r in recs)
    if not hosts or total <= 0:
        return None
    return 100.0 * sum(h.get(k, 0.0) for h in hosts for k in WORK) / total
