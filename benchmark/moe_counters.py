"""What the readers of a mixture's expert-load counters share: the step
records that carry them (`engine/stepstats.py` records of
`models/deepseek_v3.py`'s `counters=True`: `experts_touched`, distinct routed
experts summed over the expert layers and the burst's steps;
`expert_assignments`; `expert_load_max`, the fullest expert of any step and
layer of the dispatch).

Everything here returns an empty list or None where the program serves no
such field, as the commits before PR 31 and every dense model do not: a
reader then reports nothing for the cell.
"""

from __future__ import annotations


def counted(collected: dict, kind: str | None = "decode") -> list[dict]:
    """The window's step records of `kind` (None: any) with counters."""
    return [r for r in collected.get("steps") or []
            if "experts_touched" in r and kind in (None, r["kind"])]


def steps_of(rec: dict, collected: dict) -> int:
    """Model steps one record stands for: a decode burst's k, else one."""
    if rec["kind"] != "decode":
        return 1
    return max(1, rec["tokens"] // max(1, rec["active_slots"]))


def slots_per_step(collected: dict) -> int:
    """Expert slots a step could touch: expert layers x routed experts."""
    hf = collected["config"]
    layers = hf["num_hidden_layers"] - hf.get("first_k_dense_replace", 0)
    return layers * hf["n_routed_experts"]


def touched_per_step(collected: dict) -> float | None:
    """Mean distinct experts one decode step touches, summed over the
    expert layers."""
    recs = counted(collected)
    steps = sum(steps_of(r, collected) for r in recs)
    if not steps:
        return None
    return sum(r["experts_touched"] for r in recs) / steps


def traced(collected: dict) -> list[dict]:
    """The counted records of any kind whose middle lies in the traced part
    of the window (the trace's wall-clock start and stop)."""
    tr = collected.get("trace") or {}
    if "wall_start" not in tr or "wall_stop" not in tr:
        return []
    return [r for r in counted(collected, None)
            if tr["wall_start"] <= r["ts"] - r["total_s"] / 2 <= tr["wall_stop"]]
