"""Service: what one frame costs the engine's HTTP event loop — the window's
`frame_seconds_total` over `frames_total` of `/api/health .metrics.stream`:
from `Engine.stream`'s resumption with an event to the generator's resumption
after the delta's `yield`: detokenisation, the handler's JSON, `_sse_send` and
its `await resp.write`."""

from benchmark import stream_window


def read(collected: dict):
    return stream_window.ratio(collected, "frame_seconds_total",
                               "frames_total")
