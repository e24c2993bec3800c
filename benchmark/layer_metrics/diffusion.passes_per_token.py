"""Scheduler, a block family's: block passes spent per position committed —
the window's `decode` records' `row_passes` (a row-pass is one decoding row
in one pass of a burst) over their `tokens_committed` (`block_length`
positions a committed block). A block of 4 that unmasks one position a pass
costs 4 passes and 1 to commit: 1.25; one whose positions all pass the
confidence threshold at once costs 2: 0.5. Nothing to read where the
program's records carry no such counts (an autoregressive family; a commit
before PR 34)."""


def read(collected: dict):
    recs = [r for r in collected.get("steps") or []
            if r.get("kind") == "decode" and "row_passes" in r]
    committed = sum(r.get("tokens_committed", 0) for r in recs)
    if not committed:
        return None
    return sum(r["row_passes"] for r in recs) / committed
