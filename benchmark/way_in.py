"""What the readers of a request's way in share: the sampled requests joined,
by id, to the entry their first token left on a step record.

The program cuts a request's time to first token into stages, each stamped
where it ends, on the step loop's clock (`llmlb_tpu/engine/stepstats.py`
WAY_IN; docs/tracing.md "A request's way in"), and the record of the step
whose fetch brought a request's first token carries them as `first_tokens`:
one entry a request, `id` as `request_ids` writes it (the gateway's
X-Request-Id, which the load generator mints), the stages in seconds,
`chunks`, `cached_tokens`, `prefill_seq`. `collected["steps"]` holds the
window's whole records.

Everything here returns None, or nothing, where the program serves no such
field, as the commits before PR 50 do not: a reader then reports nothing for
the cell.
"""

from __future__ import annotations

from benchmark import samples, stats

# the stages that sum to the engine's own `ttft_s` (`accept` lies before it)
TTFT_STAGES = ("inbox", "place", "prefill", "first_fetch")


def first_tokens(collected: dict) -> dict[str, dict]:
    """Request id -> its entry, with the `seq`, `kind` and
    `dispatched_ahead` of the record that carried it (`fetch_*`)."""
    out: dict[str, dict] = {}
    for record in collected.get("steps") or ():
        for entry in record.get("first_tokens") or ():
            out[entry["id"]] = {
                **entry, "fetch_seq": record.get("seq"),
                "fetch_kind": record.get("kind"),
                "fetch_dispatched_ahead": record.get("dispatched_ahead")}
    return out


def joined(collected: dict) -> list[tuple[dict, dict]]:
    """(request, entry) for every sampled request that succeeded and whose
    first token a record of the window brought."""
    by_id = first_tokens(collected)
    if not by_id:
        return []
    return [(r, by_id[r["id"]]) for r in samples.ok_sample(collected)
            if r["id"] in by_id]


def stage_values(collected: dict, stage: str) -> list[float]:
    """The sample's seconds in `stage`; a request that never passed the
    stage has no value."""
    return [e[stage] for _r, e in joined(collected) if stage in e]


def stage_p50(collected: dict, stage: str) -> float | None:
    return stats.percentile(stage_values(collected, stage), 50)


def ttft_unattributed(collected: dict) -> list[float]:
    """|the engine's `ttft_s` (flight recorder, `timelines`) - the four
    stages that should sum to it|, for each sampled request that has both."""
    timelines = collected.get("timelines") or {}
    out = []
    for r, e in joined(collected):
        ttft = (timelines.get(r["id"]) or {}).get("ttft_s")
        if ttft is None or any(s not in e for s in TTFT_STAGES):
            continue
        out.append(abs(ttft - sum(e[s] for s in TTFT_STAGES)))
    return out
