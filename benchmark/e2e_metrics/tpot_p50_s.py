"""Median over the sampled requests of (last frame - first frame) /
(output tokens - 1)."""

from benchmark import samples, stats


def read(collected: dict):
    return stats.percentile(samples.tpots(collected), 50)
