"""Scheduler: the mean prefill dispatches a request took to its first token
— 1 for a one-shot prompt, its chunks for a chunked one — over the requests
whose `prefill` stage the program cut (`sched.prefill_own_mean_s`'s module
says from what): the window's difference of `prefill_cut_chunks_total` over
that of `prefill_cut_requests_total`. The number the four parts of the cut
are read against: `own` is about this many chunks' steps."""

from benchmark import manifest


def read(collected: dict):
    return manifest.load_module(
        "layer_metrics", "sched.prefill_own_mean_s").mean_part(
            collected, "chunks")
