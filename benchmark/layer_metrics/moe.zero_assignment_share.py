"""Model step, a mixture's whose router also scores zero-compute experts: of
the assignments the routers made in the window's decode steps (held, another
chip's, zero-compute), the share that went to ZERO-COMPUTE experts and cost
no product. 256 identity outputs of 768 under routing that is uniform read
33.3; what a token costs in expert products follows 100 minus it."""

from benchmark import moe_counters


def read(collected: dict):
    recs = [r for r in moe_counters.counted(collected)
            if "zero_assignments" in r]
    zero = sum(r["zero_assignments"] for r in recs)
    total = zero + sum(r["expert_assignments"] + r["assignments_elsewhere"]
                       for r in recs)
    if not total:
        return None
    return 100.0 * zero / total
