"""Scheduler: CPU seconds of the service layer's bridge threads (one blocked in
`events.get` for every stream in flight) over the window, as a percentage of
one core — the difference of `/api/health .metrics.cpu_seconds_total`
(hoststats.py: read at scrape time only) over the wall time between the two
snapshots. It is what the `run_in_executor` hop an event costs in CPU."""

from benchmark import stream_window


def read(collected: dict):
    return stream_window.cpu_share_pct(collected, "event_bridge")
