"""A request's way in, from a benchmark run's records: where the time to the
first token went, by stage and by kind of prompt (the twin of
`scripts/way_out.py`, which reads the way out).

    python3 scripts/way_in.py [--rows] .bench_run/<cell>/last_run.json [...]

Reads `last_run.json` (benchmark/run.py writes the window's whole step
records and every request's client-side stamps there). The record of the step
whose fetch brought a request's first token carries its stages as
`first_tokens` (docs/tracing.md "A request's way in": `accept`, `inbox`,
`place`, `prefill`, `first_fetch`, seconds each, stamped where each ends on
the step loop's clock; `chunks`, `cached_tokens`, `prefill_seq`). This joins
the sampled requests that succeeded to those entries by id
(`benchmark/way_in.py`, as the benchmark's readers do), and each entry to two
records by `seq`: the record of its first prefill dispatch (`prefill_seq`)
and the record that carried it (the fetch). Prints one JSON object a file:

  all, one_shot, chunked, cached   a class of prompts (one prefill dispatch;
      several; a prefix hit): `n`; the median, 90th percentile and mean of
      each stage and of their sum, in ms; the client's own time to first
      token for the same requests (first frame less the due instant);
      `prefill_cut`: the stage `prefill` by what it waited for (`own` — the
      request's own prefill steps — `others` — other prompts' — `decode` —
      the bursts between — `loop`: the entry's `prefill_cut`, PR 66) over
      the class's requests that have one: `n`, and for each part its mean
      and median in ms and `share_pct`, the part's sum over the stage's;
  join   the share of the first prefills and of the fetches that were
      dispatched ahead (`dispatched_ahead` of the two records), the decode
      records between a request's first prefill and its fetch, the `compute`
      wait of the fetch's record (the burst the token rode), and
      `queue_wait_vs_stages`: the flight recorder's admitted -> first
      `prefill_chunk` event is not in the file, so what is printed is the
      median of `inbox + place` beside the median of `inbox + place +` the
      first prefill record's time to the end of its compute — what
      `sched.queue_wait_p50_s` holds.

`--rows` adds one row a request. A commit before PR 50 serves no
`first_tokens`: every class reads `n` 0; one before PR 66 no `prefill_cut`:
that key reads null. No jax, no chip: it reads a file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import stats, way_in  # noqa: E402

STAGES = ("accept",) + way_in.TTFT_STAGES
CUT = ("own", "others", "decode", "loop")


def _ms(values: list[float], scale: float = 1e3) -> dict[str, float] | None:
    """Median, 90th percentile and mean, times `scale` (seconds to ms)."""
    if not values:
        return None
    return {"p50": round(scale * stats.percentile(values, 50), 3),
            "p90": round(scale * stats.percentile(values, 90), 3),
            "mean": round(scale * sum(values) / len(values), 3)}


def _class(pairs: list[tuple[dict, dict]]) -> dict:
    out: dict = {"n": len(pairs)}
    for stage in STAGES:
        out[stage] = _ms([e[stage] for _r, e in pairs if stage in e])
    out["engine_ttft"] = _ms([sum(e[s] for s in way_in.TTFT_STAGES)
                              for _r, e in pairs
                              if all(s in e for s in way_in.TTFT_STAGES)])
    out["client_ttft"] = _ms([r["first_s"] - r["due_s"] for r, _e in pairs])
    out["prefill_cut"] = _cut([e for _r, e in pairs if "prefill_cut" in e])
    return out


def _cut(entries: list[dict]) -> dict | None:
    """The stage `prefill` of `entries` by what it waited for."""
    if not entries:
        return None
    stage = sum(e["prefill"] for e in entries)
    out: dict = {"n": len(entries)}
    for part in CUT:
        values = [e["prefill_cut"][part] for e in entries]
        out[part] = {"mean": round(1e3 * sum(values) / len(values), 3),
                     "p50": round(1e3 * stats.percentile(values, 50), 3),
                     "share_pct": (round(100.0 * sum(values) / stage, 1)
                                   if stage > 0 else None)}
    return out


def _span(record: dict, name: str) -> float:
    return sum(dur for n, _at, dur in record.get("spans", ()) if n == name)


def _to_compute_end(record: dict) -> float:
    """Seconds from a record's begin to the end of its last `compute` span
    (where the host knows the dispatch done); its whole length without one."""
    ends = [at + dur for n, at, dur in record.get("spans", ())
            if n == "compute"]
    return max(ends) if ends else record.get("wall_s", 0.0)


def read(path: str, rows: bool = False) -> dict:
    with open(path) as f:
        run = json.load(f)
    collected = {"steps": run["steps"],
                 "sample": [r for r in run["requests"] if r["in_sample"]]}
    pairs = way_in.joined(collected)
    by_seq = {r["seq"]: r for r in run["steps"]}
    decode_seqs = sorted(r["seq"] for r in run["steps"]
                         if r["kind"] in ("decode", "verify"))
    out = {
        "file": path, "sampled": len(collected["sample"]),
        "all": _class(pairs),
        "one_shot": _class([p for p in pairs if p[1]["chunks"] == 1
                            and not p[1]["cached_tokens"]]),
        "chunked": _class([p for p in pairs if p[1]["chunks"] > 1
                           and not p[1]["cached_tokens"]]),
        "cached": _class([p for p in pairs if p[1]["cached_tokens"]]),
    }
    prefill_ahead, fetch_ahead, between, rode = [], [], [], []
    short, whole, table = [], [], []
    for r, e in pairs:
        prefill = by_seq.get(e["prefill_seq"])
        fetch = by_seq.get(e["fetch_seq"])
        fetch_ahead.append(bool(e["fetch_dispatched_ahead"]))
        if fetch is not None:
            rode.append(_span(fetch, "compute"))
        if prefill is not None:
            prefill_ahead.append(bool(prefill.get("dispatched_ahead")))
            between.append(sum(1 for s in decode_seqs
                               if e["prefill_seq"] < s <= e["fetch_seq"]))
            if "inbox" in e and "place" in e:
                short.append(e["inbox"] + e["place"])
                whole.append(short[-1] + _to_compute_end(prefill))
        if rows:
            table.append({
                "id": r["id"], "prompt_tokens": r["prompt_tokens"],
                **{s: round(1e3 * e[s], 3) for s in STAGES if s in e},
                "chunks": e["chunks"], "cached_tokens": e["cached_tokens"],
                **({"prefill_cut": {k: round(1e3 * v, 3) for k, v
                                    in e["prefill_cut"].items()}}
                   if "prefill_cut" in e else {}),
                "prefill_seq": e["prefill_seq"], "fetch_seq": e["fetch_seq"],
                "fetch_ahead": e["fetch_dispatched_ahead"],
                "client_ttft": round(1e3 * (r["first_s"] - r["due_s"]), 3)})

    def share(flags: list[bool]) -> float | None:
        return round(100.0 * sum(flags) / len(flags), 1) if flags else None

    out["join"] = {
        "first_prefills_ahead_pct": share(prefill_ahead),
        "fetches_ahead_pct": share(fetch_ahead),
        "decode_records_from_prefill_to_fetch": _ms(between, scale=1),
        "fetch_record_compute": _ms(rode),
        "queue_wait_vs_stages": {"inbox_plus_place": _ms(short),
                                 "to_first_prefill_done": _ms(whole)},
    }
    if rows:
        out["rows"] = table
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("files", nargs="+")
    ap.add_argument("--rows", action="store_true")
    args = ap.parse_args()
    for path in args.files:
        print(json.dumps(read(path, args.rows)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
