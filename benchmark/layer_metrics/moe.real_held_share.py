"""Model step, a mixture's share on one chip where the router also scores
zero-compute experts: of the assignments of REAL experts in the window's
decode steps, the share that went to experts this chip holds
(`expert_assignments`) and not to another chip's (`assignments_elsewhere`) —
`moe.held_assignment_share`'s reading, where the records also count
`zero_assignments`, which are in neither. 16 of 512 experts held under
routing that is uniform reads 3.1."""

from benchmark import manifest, moe_counters


def read(collected: dict):
    if not any("zero_assignments" in r
               for r in moe_counters.counted(collected)):
        return None
    return manifest.load_module(
        "layer_metrics", "moe.held_assignment_share").read(collected)
