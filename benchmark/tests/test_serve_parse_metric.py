"""The front end's per-layer reader, `serve_parse_p50_ms`, beside the
other serve readers: nothing under a hundred `parse` spans, their
nearest-rank median otherwise."""

import run as harness


def _run(parse_ms):
    return {"serve": {"windows": [], "spans": {"parse": [{"dur_ms": float(x)} for x in parse_ms],
                                               "queue": [{"dur_ms": 1.0}] * 500}}}


def test_reads_the_median_of_the_parse_spans():
    read = harness.load_metric("serve_parse_p50_ms").read
    assert read(_run(range(1, 202))) == 101.0
    assert read(_run([5.0] * 60 + [0.5] * 40)) == 5.0


def test_reads_nothing_under_a_hundred_spans():
    read = harness.load_metric("serve_parse_p50_ms").read
    assert read(_run(range(99))) is None
    assert read({"serve": None}) is None and read({}) is None
