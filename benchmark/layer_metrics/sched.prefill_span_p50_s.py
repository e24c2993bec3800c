"""Scheduler: median time from the begin of a request's first prefill
dispatch to the instant the host knows its prompt filled and has issued its
activation. One dispatch for a one-shot prompt; for a chunked one every
decode burst that ran between its chunks is in it (stage `prefill` of a
request's way in, benchmark/way_in.py; the entry's `chunks` tells the two
apart)."""

from benchmark import way_in


def read(collected: dict):
    return way_in.stage_p50(collected, "prefill")
