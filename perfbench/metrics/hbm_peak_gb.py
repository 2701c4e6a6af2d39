"""Device: memory_stats()["peak_bytes_in_use"] after the window, in GB.
PERF.md section 7 doubts that it counts a program's temporaries."""


def read(run):
    peak = (run.get("device") or {}).get("memory_peak_bytes")
    return peak / 1e9 if peak else None
