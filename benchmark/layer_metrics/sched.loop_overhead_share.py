"""Scheduler: the share of the loop's busy time spent outside every step —
admission, control, closing records and whatever is left — from the window's
difference of the engine's `loop_seconds_total`: (admit + control + record +
other) over (all buckets less idle)."""

from benchmark import spans


def read(collected: dict):
    return spans.busy_share_pct(collected,
                                ("admit", "control", "record", "other"))
