"""Peak bytes in use on the fullest device after the window, as the
allocator counts them, in GB (1e9)."""

META = {"layer": "device", "unit": "GB", "source": "program_counter", "better": "lower"}


def read(run: dict):
    peak = run.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
