"""Validity: median over the sampled requests of |the stage `prefill` of a
request's way in - (own + others + decode + loop)|, the four parts the
program cuts the stage into (`prefill_cut` on the request's `first_tokens`
entry, benchmark/way_in.py). The parts are differences of one loop clock's
cumulative sums, so it reads rounding (microseconds); over 0.001 means a
step of the stage that the cut does not see. Nothing to read where no
joined entry carries a cut: a commit before PR 66, or a window of prompts
that rode a burst."""

from benchmark import stats, way_in


def read(collected: dict):
    return stats.percentile(
        [abs(e["prefill"] - sum(e["prefill_cut"].values()))
         for _r, e in way_in.joined(collected)
         if "prefill_cut" in e and "prefill" in e], 50)
