"""The system under test on the serve path: `xflow serve`'s body, built
the way `xflow_tpu/serve/server.py serve_main` builds it.

With `lib/drive.py` the only file of the benchmark that imports the
program. What it takes from it: `Config`/`override`, the checkpoint
writer `train/checkpoint.py write_flat`, `ServeRunner` (`load`,
`warmup`, the compile recorder), `ServeApp`, `make_unix_server`,
`CheckpointWatcher`, and the serve stream's `kind="serve"` windows and
`kind="span"` records.
"""

from __future__ import annotations

import json
import os
import threading

from .drive import KEYMAP

CHUNK_LOG2 = 23  # slots a jitted call makes on the device on the way to the host's copy


def program_overrides(cfg: dict, ckpt_dir: str, sock: str, metrics_path: str = "") -> dict:
    pairs = {dst: cfg[src] for src, dst in KEYMAP.items() if src in cfg}
    pairs.update({
        "train.checkpoint_dir": ckpt_dir,
        "serve.unix_socket": sock,
        "serve.port": -1,  # the unix socket alone, the colocated client's path
        "serve.metrics_path": metrics_path,
    })
    pairs.update(cfg.get("program_set", {}))
    return pairs


def program_config(cfg: dict, ckpt_dir: str, sock: str, metrics_path: str = ""):
    from xflow_tpu.config import Config, override

    return override(Config(), **program_overrides(cfg, ckpt_dir, sock, metrics_path))


def write_checkpoint(cfg: dict, seed: int, width: int, ckpt_dir: str, table_fn) -> int:
    """The seed's table as a committed tables-only checkpoint (step 0),
    written by the program's own writer in the logical [slots, width]
    layout every npz checkpoint has. The values are made on the device,
    2^CHUNK_LOG2 slots a call, so the device never holds a second table.
    Returns the bytes of the table."""
    import jax
    import numpy as np

    from xflow_tpu.train.checkpoint import write_flat

    slots = 1 << int(cfg["log2_slots"])
    chunk = min(slots, 1 << CHUNK_LOG2)
    scale = float(cfg.get("v_init_scale", 0.0))
    table = np.empty((slots, width), np.float32)
    make = jax.jit(table_fn(seed, chunk, width, scale))
    for lo in range(0, slots, chunk):
        table[lo:lo + chunk] = np.asarray(make(np.uint32(lo)))
    write_flat(ckpt_dir, {f"tables/{cfg['program_table']}": table, "step": np.asarray(0, np.int32)}, 0)
    return table.nbytes


class Served:
    """A running server: runner, app, watcher, the unix-socket server
    and its thread."""

    def __init__(self, pcfg):
        from xflow_tpu.serve.runner import CheckpointWatcher, ServeRunner
        from xflow_tpu.serve.server import ServeApp, make_unix_server

        self.pcfg = pcfg
        self.runner = ServeRunner(pcfg)
        gen = self.runner.load()
        self.app = ServeApp(pcfg, self.runner)
        if self.runner.compile_recorder is not None:
            self.runner.compile_recorder.bind(self.app.metrics.appender)
        if self.app.tracer.enabled:
            self.runner.span_sink = self.app.metrics.appender
        self.rungs = self.runner.warmup()  # every rung of the ladder compiled before a request
        self.app.metrics.event("start", generation=gen.gen, step=gen.step)
        self.watcher = CheckpointWatcher(self.runner, poll_s=pcfg.serve.reload_poll_s)
        self.app.start()
        self.watcher.start()
        self.server = make_unix_server(self.app, pcfg.serve.unix_socket)
        self.thread = threading.Thread(target=self.server.serve_forever, daemon=True)
        self.thread.start()

    def compiles(self) -> int:
        rec = self.runner.compile_recorder
        return len(rec.records) if rec else 0

    def flush_window(self) -> None:
        """Close the serve stream's running window here, so that the
        records after it hold the measured window's traffic alone."""
        gen = self.runner.generation
        self.app.metrics.maybe_flush(gen.gen, gen.step, force=True)

    def describe(self) -> dict:
        rec = self.runner.compile_recorder
        last = rec.records[-1] if rec and rec.records else {}
        s = self.pcfg.serve
        return {
            "rungs": list(self.runner.rungs), "window_ms": s.window_ms, "max_batch": s.max_batch,
            "max_queue_rows": s.max_queue_rows, "autotune": s.autotune,
            "trace_sample_rate": s.trace_sample_rate,
            "predict_temp_bytes": last.get("temp_bytes"), "predict_argument_bytes": last.get("argument_bytes"),
            "predict_cache_hit": last.get("cache_hit"),
        }

    def close(self) -> None:
        self.server.shutdown()
        self.watcher.close()
        self.app.close()
        self.server.server_close()
        self.thread.join(timeout=10.0)
        sock = self.pcfg.serve.unix_socket
        if sock and os.path.exists(sock):
            os.unlink(sock)


def serve_records(path: str, offset: int = 0) -> dict:
    """The serve stream written after `offset`: `windows` (kind="serve"
    records with traffic) and `spans` (kind="span") by name."""
    out = {"windows": [], "spans": {}}
    if not path or not os.path.exists(path):
        return out
    with open(path) as f:
        f.seek(offset)
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("kind") == "serve" and "batches" in rec:
                out["windows"].append(rec)
            elif rec.get("kind") == "span":
                out["spans"].setdefault(rec.get("name"), []).append(rec)
    return out
