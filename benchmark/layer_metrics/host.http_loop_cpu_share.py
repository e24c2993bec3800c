"""Scheduler: CPU seconds of the engine's HTTP event loop (every frame's JSON and
write, every handler) over the window, as a percentage of one core — the
difference of `/api/health .metrics.cpu_seconds_total` (hoststats.py: read at
scrape time only) over the wall time between the two snapshots. Near 100 is a
saturated event loop."""

from benchmark import stream_window


def read(collected: dict):
    return stream_window.cpu_share_pct(collected, "http_loop")
