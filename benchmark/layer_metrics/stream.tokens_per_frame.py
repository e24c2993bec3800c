"""Service: tokens a frame carries — the window's `tokens_total` over
`frames_total` of `/api/health .metrics.stream`: 1.0 where every token is
its own event and frame, about 4 where a block family commits a block at
once."""

from benchmark import stream_window


def read(collected: dict):
    return stream_window.ratio(collected, "tokens_total", "frames_total")
