"""Does an executable read from the persistent compile cache hand back
arrays that report the layout they are in?

Run it TWICE on a TPU with the persistent cache on (the first run
compiles, the second reads): `python tools/layout_cache_probe.py`.
PR 32 read, on JAX 0.9.0 and a v5e: run 1 `result reports` the pinned
layout and every probe is ok; run 2 (`cache hit 1`) reports the client's
default layout for a buffer that is in the pinned one, and the probes
fail (`INVALID_ARGUMENT ... got buffer with incompatible size`,
"compiled for input layouts that disagree"). While run 2 fails,
`xflow_tpu/compile_cache.py no_persistent_cache` has to stay around
every program that hands back a pinned leaf (PERF.md section 7).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.layout import Format

    from xflow_tpu.compile_cache import enable_compile_cache, no_persistent_cache
    from xflow_tpu.train.engine import KERNEL_LAYOUT

    if jax.default_backend() != "tpu":
        print("needs a TPU: the pinned layout is a tiled one")
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    hits = []
    jax.monitoring.register_event_listener(
        lambda e, **_: hits.append(e) if e == "/jax/compilation_cache/cache_hits" else None
    )
    x = jax.device_put(jnp.arange(65536 * 88, dtype=jnp.float32).reshape(65536, 88) % 7)
    pinned = Format(KERNEL_LAYOUT, x.sharding)
    want = np.asarray(x) * 2 + 1
    with no_persistent_cache():  # a truthful input
        x = jax.block_until_ready(jax.device_put(x, pinned))
    step = jax.jit(lambda a: a * 2 + 1, in_shardings=(pinned,), out_shardings=pinned)
    compiled = step.lower(x).compile()
    r = compiled(x)
    print("cache hit", len(hits), "| output_formats", compiled.output_formats.layout)
    print("result reports", r.format.layout)
    ok = True
    for name, probe in (
        ("values", lambda: bool(np.array_equal(np.asarray(r), want))),
        ("eager r > 0", lambda: float(jnp.sum(r > 0))),
        ("pinned program again", lambda: str(compiled(r).format.layout)),
    ):
        try:
            print(name, "ok", probe())
        except Exception as e:  # noqa: BLE001 — the probe's finding, printed
            ok = False
            print(name, "FAILED", str(e)[:200].replace("\n", " "))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
