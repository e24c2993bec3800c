"""Service: how long after the scheduler finished a request its last frame
was written to the engine's socket — the window's (`stream_seconds_total`
less `made_seconds_total`) over `streams_finished_total` of `/api/health
.metrics.stream`: "made in 13.6 s, delivered over 32.8 s", split at the
engine's socket."""

from benchmark import stream_window


def read(collected: dict):
    window = stream_window.stream_window(collected)
    if window is None or not window.get("streams_finished_total"):
        return None
    return ((window["stream_seconds_total"] - window["made_seconds_total"])
            / window["streams_finished_total"])
