"""Median over the sampled requests of (last frame - due instant) / output
tokens: what a caller waits per token it asked for, time to first token
included."""

from benchmark import samples, stats


def read(collected: dict):
    return stats.percentile(samples.norm_latencies(collected), 50)
