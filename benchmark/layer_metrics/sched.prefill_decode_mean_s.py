"""Scheduler: of the stage `prefill` of a request's way in, the mean seconds
a request spent in the decode (and verify) steps between its chunks: one
burst a loop iteration while rows decode. What a prefill budget of more
than one chunk between bursts would take from a waiting prompt, and give
to the decoding rows' token gap. The part `decode` of the program's cut of
the stage, read as `sched.prefill_own_mean_s` reads `own` (its module says
from what)."""

from benchmark import manifest


def read(collected: dict):
    return manifest.load_module(
        "layer_metrics", "sched.prefill_own_mean_s").mean_part(
            collected, "decode")
