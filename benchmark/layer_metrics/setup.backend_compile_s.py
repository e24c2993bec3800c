"""Set-up: seconds in the backend before the window began — XLA compiling,
or the persistent cache handing a program back: the `backend` stage of the
engine's program ledger."""

from benchmark import spans


def read(collected: dict):
    ledger = spans.ledger_at(collected)
    return None if ledger is None else ledger["seconds_total"]["backend"]
