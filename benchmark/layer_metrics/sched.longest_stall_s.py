"""Scheduler: the longest stretch of the window in which the loop had work
and no decode or verify step ended — between the ends (`t1_s`) of two such
records that follow each other, less the idle sleep (`since_prev.idle_s`) of
every record in between, which the loop takes only when no slot is active
and nothing waits. A burst is a quarter of a second; a stall is seconds."""

from benchmark import spans


def read(collected: dict):
    records = sorted(spans.span_records(collected), key=lambda r: r["t1_s"])
    longest = None
    last_end = None
    idle = 0.0
    for r in records:
        idle += r["since_prev"]["idle_s"]
        if r["kind"] not in ("decode", "verify") or not r["active_slots"]:
            continue
        if last_end is not None:
            stretch = r["t1_s"] - last_end - idle
            longest = stretch if longest is None else max(longest, stretch)
        last_end, idle = r["t1_s"], 0.0
    return longest
