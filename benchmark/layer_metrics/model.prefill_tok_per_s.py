"""Model step: prompt tokens prefilled in the traced window (stepstats
`prefill` records) over the device time of the prefill and extend programs
in the trace."""

from benchmark import samples, stats


def read(collected: dict):
    tr = collected.get("trace") or {}
    rows = samples.matching(tr.get("modules") or {},
                            collected["settings"]["programs"]["prefill"])
    if not rows or "wall_start" not in tr:
        return None
    tokens = sum(r["tokens"] for r in collected["steps"]
                 if r["kind"] == "prefill"
                 and tr["wall_start"] <= r["ts"] <= tr["wall_stop"])
    return stats.rate(tokens, sum(r["time_s"] for r in rows)) if tokens else None
