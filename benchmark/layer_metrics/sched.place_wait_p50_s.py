"""Scheduler: median time from the loop taking a request off the inbox to
the begin of its first prefill dispatch — the wait for a slot, for pages and
for the prefill in front of it (stage `place` of a request's way in,
benchmark/way_in.py)."""

from benchmark import way_in


def read(collected: dict):
    return way_in.stage_p50(collected, "place")
