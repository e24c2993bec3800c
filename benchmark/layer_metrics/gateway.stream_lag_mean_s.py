"""Gateway: what the gateway, two HTTP hops and the client add to a whole
stream — the mean over the client's sample of (last frame less send) less the
engine's mean stream (`stream_seconds_total` over `streams_finished_total` of
the window: submitted to last frame written). Durations on each side, so no
shared clock is needed; `gateway.ttft_overhead_p50_s`'s sibling for the whole
stream."""

from benchmark import samples, stream_window


def read(collected: dict):
    engine = stream_window.ratio(collected, "stream_seconds_total",
                                 "streams_finished_total")
    client = [r["last_s"] - r["send_s"] for r in samples.ok_sample(collected)
              if r["last_s"] is not None]
    if engine is None or not client:
        return None
    return sum(client) / len(client) - engine
