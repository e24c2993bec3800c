"""Scheduler, seen from the client: the 90th percentile of first content
frame minus due instant, over the same sample as `client.ttft_p50_s`. It
reads how the scheduler shares prefill among long prompts (one 512-token
chunk of one prompt per loop iteration, round robin) and the engine's
multi-second stalls, and it does not repeat from run to run (PERF.md,
PR 23), so it stands here without a bound."""

from benchmark import samples, stats


def read(collected: dict):
    return stats.percentile(samples.ttfts(collected), 90)
