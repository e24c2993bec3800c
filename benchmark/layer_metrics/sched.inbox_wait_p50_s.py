"""Scheduler: median time a request lay in the inbox (`EngineCore.pending`)
between `admitted` and `_drain_pending` taking it (`queued`) — how long the
step loop was busy elsewhere, as a rule inside a decode burst's wait for the
device (stage `inbox` of a request's way in, benchmark/way_in.py). With
`sched.place_wait_p50_s` it is what `sched.queue_wait_p50_s` holds as one."""

from benchmark import way_in


def read(collected: dict):
    return way_in.stage_p50(collected, "inbox")
