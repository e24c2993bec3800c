"""Set-up: seconds of tracing and lowering in Python before the window
began — the `trace` and `lower` stages of the engine's program ledger
(sums of jax.monitoring events, which nest: not wall time)."""

from benchmark import spans


def read(collected: dict):
    ledger = spans.ledger_at(collected)
    if ledger is None:
        return None
    return ledger["seconds_total"]["trace"] + ledger["seconds_total"]["lower"]
