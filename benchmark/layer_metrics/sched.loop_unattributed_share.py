"""Validity: the share of the loop's busy time that no step and no named
bucket holds — `other` over (all buckets less idle), from the window's
difference of the engine's `loop_seconds_total`. Must read under 2: above
it the account of the loop's time has a hole."""

from benchmark import spans


def read(collected: dict):
    return spans.busy_share_pct(collected, ("other",))
