"""Validity: programs the engine built inside the window, by its own ledger
(`compile.programs_total` in /api/health, the window's difference). Must
read 0, and must agree with the launcher's `engine.compiles_in_window`."""


from benchmark import spans


def read(collected: dict):
    start, end = spans.ledger_at(collected), spans.ledger_at(collected, "end")
    if start is None or end is None:
        return None
    return float(end["programs_total"] - start["programs_total"])
