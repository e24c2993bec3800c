"""Model step, a mixture's: of the routed experts an expert layer holds, the mean share one
decode step touches (and so reads), over the window's decode records. At 6
of 128 experts a token, routing that is uniform touches 95% at 64 rows and
78% at 32; higher is nearer a deployment's balanced load."""

from benchmark import moe_counters


def read(collected: dict):
    touched = moe_counters.touched_per_step(collected)
    if touched is None:
        return None
    return 100.0 * touched / moe_counters.slots_per_step(collected)
