"""Scheduler: of the stage `prefill` of a request's way in, the mean seconds
a request spent in the prefill steps of OTHER prompts: the rotation among
the prefilling slots (`EngineCore._advance_prefill` feeds one chunk of one
prompt a loop iteration, round robin). Several times `own` where many
prompts prefill at once: then the rotation is the cell's time to first
token, and first come first served would halve the mean wait. The part
`others` of the program's cut of the stage, read as
`sched.prefill_own_mean_s` reads `own` (its module says from what)."""

from benchmark import manifest


def read(collected: dict):
    return manifest.load_module(
        "layer_metrics", "sched.prefill_own_mean_s").mean_part(
            collected, "others")
