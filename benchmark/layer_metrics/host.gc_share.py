"""Scheduler: the share of the window the collector ran — the difference of
`/api/health .metrics.gc.seconds_total` (`gc.callbacks`, hoststats.py) over
the wall time between the two snapshots, per cent. A collection holds the
GIL: every thread of the engine stands still for it."""

from benchmark import stream_window


def read(collected: dict):
    return stream_window.gc_share_pct(collected)
