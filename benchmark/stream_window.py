"""What the readers of a token's way out share: the window's difference of
the engine's cumulative stream counters, of its CPU seconds by thread class
and of its collector's seconds, all from `/api/health .metrics` at the
window's two ends (`llmlb_tpu/engine/streamstats.py`, `llmlb_tpu/hoststats.py`).

Everything here returns None where the program serves no such field, as the
commits before PR 36 do not: a reader then reports nothing for the cell.
"""

from __future__ import annotations

from benchmark.spans import _metrics


def _delta(collected: dict, block: str) -> dict[str, float] | None:
    """End less start of every number under `.metrics[block]`."""
    start = _metrics(collected, "start").get(block)
    end = _metrics(collected, "end").get(block)
    if not isinstance(start, dict) or not isinstance(end, dict):
        return None
    return {k: v - start.get(k, 0) for k, v in end.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def stream_window(collected: dict) -> dict[str, float] | None:
    """The stream path's counters over the window (the `*_total` keys; the
    gauges and the maximum among them are of no use as differences)."""
    return _delta(collected, "stream")


def ratio(collected: dict, part: str, whole: str,
          scale: float = 1.0) -> float | None:
    """`scale` x the window's `part` over its `whole`, both stream counters;
    None where there is no whole."""
    window = stream_window(collected)
    if window is None or not window.get(whole):
        return None
    return scale * window[part] / window[whole]


def window_seconds(collected: dict) -> float | None:
    """The wall time between the two health snapshots, by the engine's own
    uptime."""
    ups = [((collected.get(f"health_{end}") or {}).get("engine") or {})
           .get("uptime_s") for end in ("start", "end")]
    if None in ups or ups[1] <= ups[0]:
        return None
    return ups[1] - ups[0]


def _share_pct(collected: dict, block: str, key: str) -> float | None:
    window = _delta(collected, block)
    seconds = window_seconds(collected)
    if window is None or key not in window or seconds is None:
        return None
    return 100.0 * window[key] / seconds


def cpu_share_pct(collected: dict, cls: str) -> float | None:
    """The CPU seconds of thread class `cls` over the window, as a
    percentage of one core."""
    return _share_pct(collected, "cpu_seconds_total", cls)


def gc_share_pct(collected: dict) -> float | None:
    """The collector's seconds over the window, as a percentage of it."""
    return _share_pct(collected, "gc", "seconds_total")
