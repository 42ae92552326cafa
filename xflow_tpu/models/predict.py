"""The ONE pctr forward shared by offline eval and online serving.

The reference computes pCTR twice: once in the worker's predict pass
(`lr_worker.cc:207-217`) and once — re-implemented — in the serving C
API it never finished (`/root/reference/src/c_api`, disabled in its
build). Two implementations of the same sigmoid forward is exactly how
offline/online skew is born, so here the function is factored once:

    predict_fn(tables, batch_arrays) -> pctr [B]

and BOTH consumers delegate to it — `train/step.make_eval_step` (the
trainer's evaluate pass) and `serve/runner.ServeRunner` (the online
path). A serve response and an `evaluate()` probability on the same row
are the same jitted program over the same tables; the parity test in
tests/test_serve.py pins it.

The forward is `reference_pctr(model.forward(...))` — the reference's
clamped sigmoid (`base.h:54-63`) over the model's logits, consuming the
row-major batch arrays (slots/fields/mask). Sorted-plan batches work
too (the model forwards dispatch on the plan keys), but serving always
ships row-major: request batches are tiny next to training batches and
the host sort would sit on the latency path.
"""

from __future__ import annotations

from typing import Callable

import jax

from xflow_tpu.config import Config
from xflow_tpu.models.base import Model


def predict_fn(tables, batch: dict, model: Model, cfg: Config):
    """Pure (tables, batch arrays) -> pctr [B] (reference-clamped σ)."""
    from xflow_tpu.metrics import reference_pctr

    # the forward's phases as in a train step: `gather` is opened by the
    # table lookups, the rest is `rows` (telemetry.PHASE_LABELS)
    with jax.named_scope("rows"):
        return reference_pctr(model.forward(tables, batch, cfg))


def make_predict_fn(model: Model, cfg: Config, jit: bool = True,
                    recorder=None, name: str = "predict",
                    table_formats=None) -> Callable:
    """Returns pctr_step(tables, batch_arrays) -> pctr [B].

    The single factory behind `make_eval_step` AND the serve runner —
    offline eval and online serving cannot drift because they compile
    the same function. `recorder` (telemetry.CompileRecorder) routes
    the jit through the compile-accounting seam under `name`.

    `table_formats` (serve/runner.py `pinned_table_formats`; None = the
    devices' defaults) compiles the program for tables placed in those
    formats, so a call reads the table where it lies and re-lays none of
    it. The pCTRs leave in the default layout, so the program stays in
    the persistent cache (`compile_cache.no_persistent_cache`); its
    compile records say so, with the layout of each pinned table."""

    def step(tables, batch: dict):
        return predict_fn(tables, batch, model, cfg)

    if not jit:
        return step
    return jit_predict(step, recorder=recorder, name=name, table_formats=table_formats)


def jit_predict(step: Callable, recorder=None, name: str = "predict",
                table_formats=None) -> Callable:
    """`step(tables, batch)` jitted as `make_predict_fn` jits the forward:
    compiled for tables in `table_formats`, through `recorder` under
    `name`. The serve runner jits its packed-batch wrapper here."""
    fields, pinned = {}, {}
    if table_formats is not None:
        from jax.experimental.layout import Format

        pinned = {"in_shardings": (table_formats, None)}
        fields = {"persistent_cache": True, "table_layouts": {
            k: str(f.layout) for k, f in table_formats.items() if isinstance(f, Format)
        }}
    jitted = jax.jit(step, **pinned)
    return recorder.wrap(name, jitted, **fields) if recorder is not None else jitted
