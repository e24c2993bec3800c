"""Model step, a mixture's: the fullest expert's assignments in a decode dispatch (any step
of the burst, any expert layer) over the mean assignments of an expert in a
step and layer: the median over the window's decode records. 1 is perfect
balance; the products' row tiles and a sharded layer's slowest chip follow
the fullest expert."""

from benchmark import moe_counters, stats


def read(collected: dict):
    ratios = []
    for r in moe_counters.counted(collected):
        mean = r["expert_assignments"] / (
            moe_counters.steps_of(r, collected)
            * moe_counters.slots_per_step(collected))
        if mean > 0:
            ratios.append(r["expert_load_max"] / mean)
    return stats.percentile(ratios, 50) if ratios else None
