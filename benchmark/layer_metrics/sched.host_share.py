"""Scheduler: the host's share of the step loop's time in the window —
(plan + draft + host_sync + dispatch + fetch + emit) over the steps' wall
time, from /api/steps records. `compute` is the host WAITING for the device,
so it is the part left out."""

from benchmark import stats

HOST_PHASES = ("plan", "draft", "host_sync", "dispatch", "fetch", "emit")


def read(collected: dict):
    host = sum(r["phases_s"].get(p, 0.0) for r in collected["steps"]
               for p in HOST_PHASES)
    return stats.share_pct(host, sum(r["total_s"] for r in collected["steps"]))
