"""Scheduler: median wait between a request's admission and its first
prefill dispatch, raw values from the flight recorder."""

from benchmark import samples, stats


def read(collected: dict):
    out = [collected["timelines"][r["id"]]["queue_wait_s"]
           for r in samples.ok_sample(collected)
           if collected["timelines"].get(r["id"], {}).get("queue_wait_s") is not None]
    return stats.percentile(out, 50)
