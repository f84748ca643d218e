"""Peak device memory after the window (`memory_stats()`), in GB."""


def read(window: dict):
    peak = window.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
