"""Scheduler: the share of the step loop's host time in which its thread was
NOT on a CPU — waiting for the GIL or for the kernel's scheduler. Over the
window's step records: the wall time of the spans outside `compute` less
`host_cpu_s`, and, where the gap before the step held no idle sleep, the
gap's wall time less `gap_cpu_s`; over the same wall time, per cent
(engine/stepstats.py: `time.thread_time` at a step's begin, around `compute`
and at its close). `compute` is left out: there the host waits by design."""

from benchmark import spans


def read(collected: dict):
    wall = cpu = 0.0
    for r in spans.span_records(collected):
        if "host_cpu_s" not in r:
            continue
        wall += sum(dur for name, _at, dur in r["spans"] if name != "compute")
        cpu += r["host_cpu_s"]
        gap = r["since_prev"]
        if not gap.get("idle_s"):
            wall += sum(v for k, v in gap.items() if k != "idle_s")
            cpu += r.get("gap_cpu_s", 0.0)
    if wall <= 0:
        return None
    return 100.0 * max(0.0, wall - cpu) / wall
