"""Peak bytes in use on the serving chip after the window, as the
allocator counts them, in GB (1e9): the resident table; a program's
temporaries are not in that counter."""

META = {"layer": "device", "unit": "GB", "source": "program_counter", "better": "lower"}


def read(run: dict):
    peak = run.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
