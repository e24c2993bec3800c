"""Scheduler: the share of the window's decode bursts that were on the
device BEFORE their predecessor was fetched, queued behind it
(`scheduler._decode_bursts`: the order a cycle takes where no slot is free
for an arrival and nothing else needs the host) — the window's `decode`
records with `queued_behind` true over all of them, from /api/steps. A
program whose records carry no such field queues nothing, and that is its
reading: 0.0. Nothing to read where the window holds no decode record."""

from benchmark import stats


def read(collected: dict):
    decode = [r for r in collected.get("steps") or []
              if r.get("kind") == "decode"]
    queued = sum(1 for r in decode if r.get("queued_behind"))
    return stats.share_pct(queued, len(decode))
