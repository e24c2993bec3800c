"""Model step, a mixture's share on one chip: of the assignments the routers
made in the window's decode steps, the share that went to experts this chip
holds (`expert_assignments`) and not to another chip's
(`assignments_elsewhere`). Half the experts held under routing that is
uniform reads 50; what the chip computes of a token's experts follows it."""

from benchmark import moe_counters


def read(collected: dict):
    recs = [r for r in moe_counters.counted(collected)
            if "assignments_elsewhere" in r]
    here = sum(r["expert_assignments"] for r in recs)
    total = here + sum(r["assignments_elsewhere"] for r in recs)
    if not total:
        return None
    return 100.0 * here / total
