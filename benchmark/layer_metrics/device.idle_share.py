"""Device: the share of the traced window in which no operation ran on the
device — 1 minus the union of the device-op intervals over the window."""


def read(collected: dict):
    tr = collected.get("trace") or {}
    if not tr.get("busy_s") or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
