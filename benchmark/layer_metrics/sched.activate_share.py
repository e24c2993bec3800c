"""Scheduler: the share of the steps' wall time spent activating prefilled
requests — the `activate` spans (`_activate_group`: first-token sample, the
scatters of the sampling state, stamped by the step's own span helper) over
the sum of `wall_s`, from the window's /api/steps records."""

from benchmark import spans, stats


def read(collected: dict):
    records = spans.span_records(collected)
    if not records:
        return None
    activate = sum(dur for r in records for name, _at, dur in r["spans"]
                   if name == "activate")
    return stats.share_pct(activate, sum(r["wall_s"] for r in records))
