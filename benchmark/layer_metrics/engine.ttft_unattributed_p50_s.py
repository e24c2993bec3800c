"""Validity: median over the sample of |the engine's own `ttft_s` (the flight
recorder's `finished` event) - (inbox + place + prefill + first_fetch)|, the
four stages of a request's way in that should sum to it
(benchmark/way_in.py). The stamps telescope, so it reads rounding (under a
millisecond); a larger reading means a path to a first token that is not
stamped."""

from benchmark import stats, way_in


def read(collected: dict):
    return stats.percentile(way_in.ttft_unattributed(collected), 50)
