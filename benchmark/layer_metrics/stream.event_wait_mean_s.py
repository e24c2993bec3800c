"""Service: how long an event (one token, or a block family's several) lay in
`Request.events` before the service's consumer took it — the window's
`event_wait_seconds_total` over `events_total` of `/api/health
.metrics.stream` (engine/streamstats.py EventQueue: stamped at the put, read
where `Engine.stream` resumes with the event on the event loop, so the
`run_in_executor` hop is in it)."""

from benchmark import stream_window


def read(collected: dict):
    return stream_window.ratio(collected, "event_wait_seconds_total",
                               "events_total")
