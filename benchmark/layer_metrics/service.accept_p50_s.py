"""Service: median time from the HTTP handler's entry to `EngineCore.submit`'s
`admitted` — reading the body, the chat template, tokenisation, validation,
the adapter pin — stamped by `engine/server.py` (entry) and the scheduler's
`submit` (stage `accept` of a request's way in, benchmark/way_in.py)."""

from benchmark import way_in


def read(collected: dict):
    return way_in.stage_p50(collected, "accept")
