"""Scheduler: median time from a request's activation to its first token
reaching the host. An activation leaves the sampled token on the device and
it is fetched as row 0 of the NEXT decode burst, so this is the burst a
first token rides behind its prefill (stage `first_fetch` of a request's way
in, benchmark/way_in.py)."""

from benchmark import way_in


def read(collected: dict):
    return way_in.stage_p50(collected, "first_fetch")
