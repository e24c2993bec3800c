"""Model step: median device duration of one step of the burst decode
program — the program's executions in the device trace, over the burst's
steps."""

from benchmark import samples, stats


def read(collected: dict):
    tr = collected.get("trace") or {}
    rows = samples.matching(tr.get("modules") or {},
                            collected["settings"]["programs"]["decode"])
    if not rows:
        return None
    # the programs differ by context window; weigh each by its executions
    meds = [r["median_s"] for r in rows for _ in range(r["count"])]
    return stats.percentile(meds, 50) / max(1, collected["engine"]["decode_burst"] or 1)
