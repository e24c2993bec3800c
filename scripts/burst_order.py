"""The host's stretch between two decode bursts, from a benchmark run's step
records: which order each burst and each prefill took and what the host did
where.

    python3 scripts/burst_order.py .bench_run/<cell>/last_run.json [...]

Reads the `steps` of `last_run.json` (benchmark/run.py writes the window's
stepstats records there) and prints one JSON object a file: the decode
records' count (a dense burst's and, since PR 51, a block family's: both
carry the field), the share dispatched ahead (`dispatched_ahead`,
engine/scheduler.py `_decode_bursts`), the share QUEUED BEHIND their
predecessor before its fetch (`queued_behind`, since PR 60) and the reasons
of the others, the mean milliseconds a decode record spends in each span —
exposed (`host_sync`, `dispatch`, `fetch`, `emit`, and the gap before the
record by bucket) against in flight (`host_sync_inflight`, `emit_inflight`,
`dispatch_inflight`, `fetch_inflight`) — and what a queued record exposes
against one that left ahead (`queued_ms`, `ahead_ms`: a queued burst's
record begins at its predecessor's fetch and, where its successor is queued
in its turn, exposes nothing), and
the same for the prefill records; then the share of the one-shot prefill
records dispatched ahead (`_admit_ahead`: the prefill left before the burst
in front of it was emitted) and, for those and for the others apart, the
milliseconds a prefill record exposes (its spans other than `compute` and
the in-flight ones, and the gap before it) against those it spends in
flight (`activate_inflight`; `compute` is the host waiting). A commit that
has no `dispatched_ahead` on its decode records (before PR 39) reads as
"ahead" 0 with no reasons; one that has no `queued_behind` (before PR 60)
reads "queued" 0; one that has none on its prefill records (before
PR 49; a chunk's record never has one) reads `prefill_ahead_share_pct` null.
No jax, no chip: it reads a file.
"""

from __future__ import annotations

import json
import sys
from collections import Counter


INFLIGHT = ("host_sync_inflight", "emit_inflight", "activate_inflight",
            "dispatch_inflight", "fetch_inflight")


def _exposed_ms(records: list[dict]) -> dict[str, float] | None:
    """Mean milliseconds a record keeps the device waiting for the host
    (spans outside `compute` and INFLIGHT, and the gap before it less the
    idle sleep) against those the host works with a program in flight."""
    if not records:
        return None
    exposed = inflight = 0.0
    for r in records:
        for name, _at, dur in r.get("spans", ()):
            if name in INFLIGHT:
                inflight += dur
            elif name != "compute":
                exposed += dur
        gap = r.get("since_prev") or {}
        exposed += sum(dur for bucket, dur in gap.items()
                       if bucket != "idle_s")
    n = len(records)
    return {"exposed": round(1e3 * exposed / n, 3),
            "in_flight": round(1e3 * inflight / n, 3)}


def _mean_ms(records: list[dict]) -> dict[str, float]:
    total: Counter = Counter()
    for r in records:
        for name, _at, dur in r.get("spans", ()):
            total[name] += dur
        for bucket, dur in (r.get("since_prev") or {}).items():
            if bucket != "idle_s":
                total[f"gap.{bucket}"] += dur
    n = max(1, len(records))
    return {name: round(1e3 * v / n, 3) for name, v in sorted(total.items())}


def summarize(path: str) -> dict:
    with open(path) as f:
        run = json.load(f)
    steps = run["steps"]
    decode = [r for r in steps if r["kind"] == "decode"]
    prefill = [r for r in steps if r["kind"] == "prefill"]
    bursts = [r for r in decode if "dispatched_ahead" in r]
    ahead = [r for r in bursts if r["dispatched_ahead"]]
    queued = [r for r in bursts if r.get("queued_behind")]
    held = [r for r in bursts
            if not r["dispatched_ahead"] and not r.get("queued_behind")]
    groups = [r for r in prefill if "dispatched_ahead" in r]
    groups_ahead = [r for r in groups if r["dispatched_ahead"]]
    out = {
        "file": path,
        "workload": run["args"].get("workload"),
        "decode_records": len(decode),
        "prefill_records": len(prefill),
        "ahead": len(ahead),
        "ahead_share_pct": (round(100.0 * len(ahead) / len(bursts), 1)
                            if bursts else None),
        "queued": len(queued),
        "queued_share_pct": (round(100.0 * len(queued) / len(bursts), 1)
                             if bursts else None),
        "not_ahead": dict(Counter(r["ahead_blocked_by"] for r in held)),
        "queued_ms": _exposed_ms(queued),
        "ahead_ms": _exposed_ms(ahead),
        "not_ahead_ms": _exposed_ms(held),
        "decode_wall_ms": round(1e3 * sum(r["wall_s"] for r in decode)
                                / max(1, len(decode)), 3),
        "decode_mean_ms": _mean_ms(decode),
        "prefill_mean_ms": _mean_ms(prefill),
        "prefill_ahead": len(groups_ahead),
        "prefill_ahead_share_pct": (
            round(100.0 * len(groups_ahead) / len(groups), 1)
            if groups else None),
        "prefill_ahead_ms": _exposed_ms(groups_ahead),
        "prefill_not_ahead_ms": _exposed_ms(
            [r for r in prefill if not r.get("dispatched_ahead")]),
    }
    if queued:
        out["queued_mean_ms"] = _mean_ms(queued)
    if ahead:
        out["ahead_mean_ms"] = _mean_ms(ahead)
        out["not_ahead_mean_ms"] = _mean_ms(held)
    return out


def main(argv: list[str]) -> int:
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    for path in argv:
        print(json.dumps(summarize(path)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
