"""Rows answered over the padded rows of the batches that carried them
(batches x rung), from the window's `kind="serve"` records: how well
the coalescer fills the compiled shape."""

META = {"layer": "coalescer", "unit": "%", "source": "program_counter", "better": "higher"}


def read(run: dict):
    windows = [w for w in (run.get("serve") or {}).get("windows", []) if w.get("batches") and w.get("batch_fill")]
    if not windows:
        return None
    rows = sum(w["rows"] for w in windows)
    padded = sum(w["rows"] / w["batch_fill"] for w in windows)
    return 100.0 * rows / padded
