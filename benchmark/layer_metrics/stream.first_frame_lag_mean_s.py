"""Service: how long after the scheduler put a stream's first content event
its first frame was written to the engine's socket — the window's
`first_frame_seconds_total` over `first_frames_total` of `/api/health
.metrics.stream` (engine/streamstats.py: the event's put stamp against the
generator's resumption after the first delta's `yield`). The last stage of a
request's way in, on the HTTP event loop's side."""

from benchmark import stream_window


def read(collected: dict):
    return stream_window.ratio(collected, "first_frame_seconds_total",
                               "first_frames_total")
