"""Set-up: seconds of building, all stages, on the window-prewarm thread
(`_prewarm_windows`) before the window began — the `prewarm` thread class
of the engine's program ledger. What it builds the dispatch builds again."""

from benchmark import spans


def read(collected: dict):
    ledger = spans.ledger_at(collected)
    if ledger is None:
        return None
    return sum(ledger["by_thread"]["prewarm"]["seconds_total"].values())
