"""Runs of one cell, one after another in one call, as the bounds are set
from: `--sets` sets of `--runs` runs with the same seeds in every set, then
optionally one traced run; every final line is appended to
`chiprun_out/bench/<cell>.jsonl`, and the spread of each metric (quartile
distance over the median, `statistics.quantiles(n=4)`) is printed per set.

    chiprun --timeout 3600 -- python3 benchmark/measure.py \
        --workload mistral-7b-l16.chat-paced --sets 2 --runs 6 --traced 1

A tool for the PR that defines or extends the benchmark; the driver does not
call it. It never imports jax.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import manifest as mf  # noqa: E402
from benchmark import stats  # noqa: E402


def one_run(workload: str, seed: int, seconds: int, trace: int, extra: list[str],
            out_dir: str, tag: str) -> dict | None:
    start = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace),
         *extra], cwd=ROOT, capture_output=True, text=True)
    wall = time.monotonic() - start
    lines = proc.stdout.strip().splitlines()
    with open(os.path.join(out_dir, f"{workload}.{tag}.stderr"), "w") as f:
        f.write(proc.stderr[-20000:])
    if proc.returncode != 0 or not lines:
        print(f"run {tag} failed with code {proc.returncode}:\n"
              + proc.stderr[-3000:], flush=True)
        return None
    line = json.loads(lines[-1])
    rec = {"workload": workload, "tag": tag, "seed": seed, "seconds": seconds,
           "trace": trace, "wall_s": wall, "line": line,
           "diag": json.loads(lines[-2]) if len(lines) > 1 else None}
    with open(os.path.join(out_dir, f"{workload}.jsonl"), "a") as f:
        f.write(json.dumps(rec) + "\n")
    last = os.path.join(ROOT, ".bench_run", workload, "last_run.json")
    if os.path.isfile(last):  # per-request records, for looking at a spread
        os.replace(last, os.path.join(out_dir, f"{workload}.{tag}.requests.json"))
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--traced", type=int, default=0)
    ap.add_argument("--first-seed", type=int, default=2147483700)
    ap.add_argument("--extra", default="", help="more arguments for run.py")
    args = ap.parse_args()
    seconds = args.seconds or mf.load()["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", "bench")
    os.makedirs(out_dir, exist_ok=True)
    extra = args.extra.split() if args.extra else []
    seeds = [args.first_seed + 7919 * i for i in range(args.runs)]
    sets: list[list[dict]] = []
    for s in range(args.sets):
        recs = []
        for i, seed in enumerate(seeds):
            rec = one_run(args.workload, seed, seconds, 0, extra, out_dir,
                          f"set{s}.run{i}")
            if rec:
                recs.append(rec)
                m = {k: round(v["value"], 5) for k, v in rec["line"]["metrics"].items()}
                print(f"set {s} run {i} seed {seed} wall {rec['wall_s']:.0f}s "
                      f"correct={rec['line']['correct']} "
                      f"attempted={rec['line']['attempted']} "
                      f"failed={rec['line']['failed']} "
                      f"compiles={rec['diag']['compiles_in_window']} {m}",
                      flush=True)
        sets.append(recs)
    for t in range(args.traced):
        rec = one_run(args.workload, seeds[0] + 1 + t, seconds, 1,
                      extra + ["--dump-trace-structure", os.path.join(
                          out_dir, f"{args.workload}.trace_structure.json")],
                      out_dir, f"traced{t}")
        if rec:
            print("traced:", json.dumps(rec["line"]), flush=True)
    print("\nspread per set (quartile distance / median), first run of the "
          "call left out of setup_s:")
    names = sorted({k for recs in sets for r in recs for k in r["line"]["metrics"]})
    for name in names:
        row = []
        for s, recs in enumerate(sets):
            vals = [r["line"]["metrics"][name]["value"] for r in recs
                    if name in r["line"]["metrics"]]
            if name == "setup_s" and s == 0:
                vals = vals[1:]
            sp = stats.spread(vals)
            row.append(f"set{s}: median {stats.median(vals):.5g} spread "
                       f"{'n/a' if sp is None else f'{100 * sp:.2f}%'} "
                       f"min {min(vals):.5g} max {max(vals):.5g}")
        print(f"{name:16s} " + " | ".join(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
