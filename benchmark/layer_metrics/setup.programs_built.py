"""Set-up: programs the engine had built when the window began (compiled,
or fetched from the persistent cache: one backend-compile event each) — the
engine's own ledger, `compile.programs_total` in /api/health at the
window's first instant."""

from benchmark import spans


def read(collected: dict):
    ledger = spans.ledger_at(collected)
    return None if ledger is None else float(ledger["programs_total"])
