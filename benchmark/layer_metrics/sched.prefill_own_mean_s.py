"""Scheduler: of the stage `prefill` of a request's way in (first prefill
dispatch -> the host knows the prompt filled), the mean seconds a request
spent in ITS OWN prefill steps — dispatch, compute and emit of each of its
chunks, the last one up to the stamp that ends the stage. The program cuts
the stage by what it waited for (`llmlb_tpu/engine/stepstats.py`
PREFILL_CUT: `own`, `others`, `decode`, `loop`, summing to the stage by
construction; docs/tracing.md "A request's way in") and sums the parts over
every request that reached its first token, in `/api/health
.metrics.way_in`: `prefill_cut_seconds_total{part}`,
`prefill_cut_requests_total`, `prefill_cut_chunks_total`. A reading is the
difference between the window's two ends over the requests it admitted — a
mean from the counters and not a median over the sample, which in a cell
of long prompts joins a handful of requests where the counters hold every
admission of the window.

The four `sched.prefill_*_mean_s` readers and `sched.prefill_chunks_mean`
share `window` and `mean_part` below. Nothing to read where the program
serves no such counter (a commit before PR 66) or the window brought no
request with a cut to its first token."""



def _way_in(collected: dict, end: str) -> dict:
    health = collected.get(f"health_{end}") or {}
    return (health.get("metrics") or {}).get("way_in") or {}


def window(collected: dict) -> dict | None:
    """The window's difference of the cut's counters: `requests`, `chunks`
    and the seconds by part."""
    start, end = _way_in(collected, "start"), _way_in(collected, "end")
    if "prefill_cut_requests_total" not in end:
        return None
    seconds = end["prefill_cut_seconds_total"]
    before = start.get("prefill_cut_seconds_total") or {}
    return {
        "requests": (end["prefill_cut_requests_total"]
                     - start.get("prefill_cut_requests_total", 0)),
        "chunks": (end["prefill_cut_chunks_total"]
                   - start.get("prefill_cut_chunks_total", 0)),
        **{part: v - before.get(part, 0.0) for part, v in seconds.items()}}


def mean_part(collected: dict, part: str) -> float | None:
    """`part` of the window's difference, a request."""
    diff = window(collected)
    if diff is None or diff["requests"] <= 0:
        return None
    return diff[part] / diff["requests"]


def read(collected: dict):
    return mean_part(collected, "own")
