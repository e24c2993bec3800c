"""Scheduler, seen from the client of an open loop: the median of first
content frame minus due instant. It does not repeat well enough for a bound
of its own (the wait for the running 8-step burst is a draw per request, and
140 of them leave the median a quartile distance of 3-10% from run to run:
PERF.md, PR 23), so it stands here, recorded by every PR; the bounded metric
it moves is `norm_latency_p50_s`, of which it is about an eighth."""

from benchmark import samples, stats


def read(collected: dict):
    return stats.percentile(samples.ttfts(collected), 50)
