"""Scheduler: of the stage `prefill` of a request's way in, the mean seconds
a request spent in the loop BETWEEN steps: admission, the closing of
records, control, the idle sleep, whatever no step holds (the gap buckets
of `stepstats.LoopClock`). Small, or the loop itself holds prompts back.
The part `loop` of the program's cut of the stage, read as
`sched.prefill_own_mean_s` reads `own` (its module says from what)."""

from benchmark import manifest


def read(collected: dict):
    return manifest.load_module(
        "layer_metrics", "sched.prefill_own_mean_s").mean_part(
            collected, "loop")
