"""What the readers of the step loop's measured timeline share: the window's
difference of the loop's cumulative buckets and of the program ledger, both
from the engine's `/api/health .metrics` at the window's two ends
(`llmlb_tpu/engine/stepstats.py` LoopClock, `llmlb_tpu/engine/compilelog.py`).

Everything here returns None where the program serves no such field, as the
commits before PR 24 do not: a reader then reports nothing for the cell.
"""

from __future__ import annotations

def _metrics(collected: dict, end: str) -> dict:
    return (collected.get(f"health_{end}") or {}).get("metrics") or {}


def loop_window(collected: dict) -> dict[str, float] | None:
    """Seconds of the window by bucket (step, admit, control, record, idle,
    other), summed over the engine's loops."""
    start = _metrics(collected, "start").get("loop_seconds_total")
    end = _metrics(collected, "end").get("loop_seconds_total")
    if not start or not end:
        return None
    out: dict[str, float] = {}
    for tag, buckets in end.items():
        for bucket, seconds in buckets.items():
            before = (start.get(tag) or {}).get(bucket, 0.0)
            out[bucket] = out.get(bucket, 0.0) + seconds - before
    return out


def busy_share_pct(collected: dict, buckets: tuple[str, ...]) -> float | None:
    """`buckets` as a percentage of the window's time less the idle sleep."""
    window = loop_window(collected)
    if window is None:
        return None
    busy = sum(window.values()) - window.get("idle", 0.0)
    if busy <= 0:
        return None
    return 100.0 * sum(window.get(b, 0.0) for b in buckets) / busy


def ledger_at(collected: dict, end: str = "start") -> dict | None:
    """The engine's program ledger as it stood at the window's "start" (all
    of set-up) or its "end"."""
    return _metrics(collected, end).get("compile")


def span_records(collected: dict) -> list[dict]:
    """The window's step records that carry measured spans."""
    return [r for r in collected.get("steps") or [] if "spans" in r]
