"""Gateway: what the gateway (and the HTTP hop) adds to time to first token —
the client's first frame minus its send, less the engine's own `ttft_s` for
the same request id (flight recorder). Median over the sample."""

from benchmark import samples, stats


def read(collected: dict):
    out = []
    for r in samples.ok_sample(collected):
        tl = collected["timelines"].get(r["id"])
        if tl and tl.get("ttft_s") is not None:
            out.append((r["first_s"] - r["send_s"]) - tl["ttft_s"])
    return stats.percentile(out, 50)
