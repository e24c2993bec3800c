"""Scheduler: CPU seconds of the step loop's thread (split mode's two) over the
window, as a percentage of one core — the difference of `/api/health
.metrics.cpu_seconds_total` (hoststats.py: read at scrape time only) over the
wall time between the two snapshots. Against the loop's busy time it says how
much of that was work."""

from benchmark import stream_window


def read(collected: dict):
    return stream_window.cpu_share_pct(collected, "step_loop")
