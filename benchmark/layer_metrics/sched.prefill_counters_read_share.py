"""Scheduler: the share of the prefill steps' wall time spent reading the
family's step counters back to the host — the `counters` spans
(`scheduler._prefill_counters`: one blocking device-to-host read an array,
eight for `models/dots3_note.py`, at the end of every prefill step served
in today's order, tracing on or off) over the sum of `wall_s` of the
window's `prefill` records. The instrument's own cost on the hot path: a
burst's counters ride its one token fetch, a prefill's do not. Nothing to
read where no prefill record of the window has such a span: a commit before
PR 66, or a family without counters."""

from benchmark import spans, stats


def read(collected: dict):
    records = [r for r in spans.span_records(collected)
               if r.get("kind") == "prefill"]
    reads = [dur for r in records for name, _at, dur in r["spans"]
             if name == "counters"]
    if not reads:
        return None
    return stats.share_pct(sum(reads), sum(r["wall_s"] for r in records))
