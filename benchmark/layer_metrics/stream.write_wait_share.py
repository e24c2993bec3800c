"""Service: the share of the frames' time spent in writes that found the
connection paused — `write_wait_seconds_total` over `frame_seconds_total` of
`/api/health .metrics.stream`, per cent. aiohttp awaits a write only while
the transport holds writing paused, which is TCP back-pressure: the reader
downstream (gateway, client) is slower than the engine writes."""

from benchmark import stream_window


def read(collected: dict):
    return stream_window.ratio(collected, "write_wait_seconds_total",
                               "frame_seconds_total", scale=100.0)
