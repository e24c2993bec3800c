"""Validity: how late the generator ran — actual send minus due instant,
99th percentile over the sample. A starved generator must not be read as a
fast server."""

from benchmark import stats


def read(collected: dict):
    return stats.percentile([r["send_s"] - r["due_s"] for r in collected["sample"]], 99)
