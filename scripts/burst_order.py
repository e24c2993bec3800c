"""The host's stretch between two decode bursts, from a benchmark run's step
records: which order each burst took and what the host did where.

    python3 scripts/burst_order.py .bench_run/<cell>/last_run.json [...]

Reads the `steps` of `last_run.json` (benchmark/run.py writes the window's
stepstats records there) and prints one JSON object a file: the decode
records' count, the share dispatched ahead (`dispatched_ahead`,
engine/scheduler.py `_decode_bursts`) and the reasons of the others, the mean
milliseconds a decode record spends in each span — exposed (`host_sync`,
`dispatch`, `fetch`, `emit`, and the gap before the record by bucket) against
in flight (`host_sync_inflight`, `emit_inflight`) — and the same for the
prefill records. A commit that has no such field (before PR 39) reads as
"ahead" 0 with no reasons. No jax, no chip: it reads a file.
"""

from __future__ import annotations

import json
import sys
from collections import Counter


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
    out = {
        "file": path,
        "workload": run["args"].get("workload"),
        "decode_records": len(decode),
        "prefill_records": len(prefill),
        "ahead": len(ahead),
        "ahead_share_pct": (round(100.0 * len(ahead) / len(bursts), 1)
                            if bursts else None),
        "not_ahead": dict(Counter(r["ahead_blocked_by"] for r in bursts
                                  if not r["dispatched_ahead"])),
        "decode_wall_ms": round(1e3 * sum(r["wall_s"] for r in decode)
                                / max(1, len(decode)), 3),
        "decode_mean_ms": _mean_ms(decode),
        "prefill_mean_ms": _mean_ms(prefill),
    }
    if ahead:
        out["ahead_mean_ms"] = _mean_ms(ahead)
        out["not_ahead_mean_ms"] = _mean_ms(
            [r for r in bursts if not r["dispatched_ahead"]])
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
