"""Scheduler: CPU seconds of the engine's whole process (every thread, XLA's own
among them) over the window, as a percentage of one core — the difference of
`/api/health .metrics.cpu_seconds_total` (hoststats.py: read at scrape time
only) over the wall time between the two snapshots. Near 100 with several
classes busy is a saturated GIL."""

from benchmark import stream_window


def read(collected: dict):
    return stream_window.cpu_share_pct(collected, "process")
