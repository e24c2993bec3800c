"""Device: `memory_stats()["peak_bytes_in_use"]` of the fullest chip, after
the window."""


def read(collected: dict):
    return collected["device"].get("memory_peak_bytes") or None
