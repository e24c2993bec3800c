"""Scheduler: the share of the window's admissions whose prompt RODE a decode
burst — the burst's first step carried it, in the same pass over the weights
as the rows' tokens, and no prefill and no activation was dispatched for it
(`scheduler._admit_riding`, docs/scheduling.md "An arrival rides a burst").
From /api/steps: the window's `decode` records with `admitted`, over the
window's admissions, which are the entries of its records' `first_tokens` —
a request's first token is on the record whose fetch brought it, whichever
way the request came in, a riding one's on the burst that admitted it — so a
chunked prompt's three `prefill` records are one admission, as its request
is one. A program whose records carry no `admitted` let nothing ride, and
that is its reading: 0.0, also where the window admitted nobody. Nothing to
read where the window holds no decode record."""

from benchmark import stats


def read(collected: dict):
    steps = collected.get("steps") or []
    decode = [r for r in steps if r.get("kind") == "decode"]
    if not decode:
        return None
    rode = sum(1 for r in decode if r.get("admitted"))
    # (a rider cancelled before its first token left no entry: its record
    # counts for it)
    admissions = sum(max(len(r.get("first_tokens") or ()),
                         1 if r.get("admitted") else 0) for r in steps)
    return stats.share_pct(rode, admissions) or 0.0
