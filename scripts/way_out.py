#!/usr/bin/env python3
"""A token's way out, read by hand around one run of the benchmark.

    python3 scripts/way_out.py --out chiprun_out/way_out/<name>.json -- \
        python3 benchmark/run.py --workload <cell> --seed <n> --trace 0

Runs the command after `--` and, every `--every` seconds while it runs, reads
what the harness does not collect (PERF.md §7): the gateway's `/metrics`
(the relay's chunks, bytes and seconds by phase, its CPU seconds), the
engine's `/api/health .metrics` (`stream`, `cpu_seconds_total`, `gc`,
`tokens_total`) and the CPU time of the benchmark's own process, the
single-process SSE client (`/proc/<pid>/stat`). It finds the two servers by
their command lines under the command's process. At the end it prints the
table of PERF.md §5 "a token's way out" over the steady stretch: the
samples in which the engine made tokens at nine tenths or more of its best
rate between two samples.

The readings are per second of that stretch; a share is of one core. Never
imports jax: the chip belongs to the engine.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time
import urllib.request

_TICK = os.sysconf("SC_CLK_TCK")


def _children(pid: int) -> list[int]:
    out = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, IndexError, ValueError):
            continue
        if ppid == pid:
            out.append(int(entry))
    return out


def _cmdline(pid: int) -> list[str]:
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return f.read().decode(errors="replace").split("\0")
    except OSError:
        return []


def _cpu_seconds(pid: int) -> float | None:
    """utime + stime of a process, all its threads."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / _TICK
    except (OSError, IndexError, ValueError):
        return None


def _port(argv: list[str]) -> int | None:
    if "--port" in argv:
        return int(argv[argv.index("--port") + 1])
    return None


def find_servers(root: int) -> dict:
    """{"client": pid of run.py, "engine": port, "gateway": port} as far as
    they have started."""
    found: dict = {}
    todo = [root]
    while todo:
        pid = todo.pop()
        argv = _cmdline(pid)
        joined = " ".join(argv)
        if "benchmark/run.py" in joined or joined.endswith("run.py"):
            found.setdefault("client", pid)
        if "launcher.py" in joined and _port(argv):
            found["engine"], found["engine_pid"] = _port(argv), pid
        if "llmlb_tpu.gateway.server" in joined and _port(argv):
            found["gateway"], found["gateway_pid"] = _port(argv), pid
        todo.extend(_children(pid))
    return found


def _get(url: str) -> bytes | None:
    try:
        with urllib.request.urlopen(url, timeout=2) as r:
            return r.read()
    except Exception:
        return None


_SAMPLE = re.compile(r"^(llmlb_gateway_(?:relay|cpu)_[a-z_]+)"
                     r"(?:\{[a-z]+=\"([a-z_]+)\"\})? (\S+)$", re.MULTILINE)


def sample(found: dict) -> dict | None:
    out: dict = {"t": time.monotonic()}
    raw = _get(f"http://127.0.0.1:{found['engine']}/api/health")
    if raw is None:
        return None
    health = json.loads(raw)
    m = health.get("metrics") or {}
    out["engine"] = {k: m.get(k) for k in
                     ("stream", "cpu_seconds_total", "gc", "tokens_total")}
    out["engine"]["active_slots"] = health["engine"]["active_slots"]
    raw = _get(f"http://127.0.0.1:{found['gateway']}/metrics")
    if raw is not None:
        out["gateway"] = {(name + ("." + label if label else "")): float(v)
                          for name, label, v in _SAMPLE.findall(raw.decode())}
    for who in ("client", "engine_pid", "gateway_pid"):
        out[f"{who.removesuffix('_pid')}_cpu_s"] = _cpu_seconds(found[who])
    return out


def table(samples: list[dict]) -> dict:
    """Rates and means over the steady stretch (module docstring)."""
    pairs = [(a, b) for a, b in zip(samples, samples[1:])
             if b["engine"]["tokens_total"] is not None]
    rates = [(b["engine"]["tokens_total"] - a["engine"]["tokens_total"])
             / (b["t"] - a["t"]) for a, b in pairs]
    if not rates or max(rates) <= 0:
        return {}
    steady = [i for i, r in enumerate(rates) if r >= 0.9 * max(rates)]
    a, b = pairs[steady[0]][0], pairs[steady[-1]][1]
    dt = b["t"] - a["t"]

    def d(path: str) -> float | None:
        va, vb = a, b
        for key in path.split("/"):
            va = (va or {}).get(key) if isinstance(va, dict) else None
            vb = (vb or {}).get(key) if isinstance(vb, dict) else None
        return None if va is None or vb is None else vb - va

    def per(num: str, den: str, scale: float = 1.0):
        n, q = d(num), d(den)
        return None if n is None or not q else scale * n / q

    def share(path: str):
        v = d(path)
        return None if v is None else 100.0 * v / dt

    g = "gateway/llmlb_gateway_"
    s = "engine/stream/"
    out = {
        "seconds": dt, "samples": steady[-1] - steady[0] + 2,
        "active_slots": b["engine"]["active_slots"],
        "made_tok_per_s": per("engine/tokens_total", "t"),
        "taken_tok_per_s": per(s + "tokens_total", "t"),
        "frames_per_s": per(s + "frames_total", "t"),
        "event_wait_mean_s": per(s + "event_wait_seconds_total",
                                 s + "events_total"),
        "event_backlog_max": b["engine"]["stream"]
        and b["engine"]["stream"].get("event_backlog_max"),
        "events_queued_at_end": b["engine"]["stream"]
        and b["engine"]["stream"].get("events_queued"),
        "frame_cost_mean_s": per(s + "frame_seconds_total",
                                 s + "frames_total"),
        "loop_busy_by_frames_pct": share(s + "frame_seconds_total"),
        "write_wait_share_pct": per(s + "write_wait_seconds_total",
                                    s + "frame_seconds_total", 100.0),
        "delivery_lag_mean_s": (
            None if not d(s + "streams_finished_total") else
            (d(s + "stream_seconds_total") - d(s + "made_seconds_total"))
            / d(s + "streams_finished_total")),
        "engine_cpu_pct": {k: share(f"engine/cpu_seconds_total/{k}")
                           for k in (a["engine"]["cpu_seconds_total"] or {})},
        "engine_gc_pct": share("engine/gc/seconds_total"),
        "gateway_chunks_per_s": per(g + "relay_chunks_total", "t"),
        "gateway_bytes_per_chunk": per(g + "relay_bytes_total",
                                       g + "relay_chunks_total"),
        "gateway_relay_pct": {p: share(g + "relay_seconds_total." + p)
                              for p in ("upstream_wait", "feed",
                                        "client_write")},
        "gateway_cpu_pct": {k: share(g + "cpu_seconds_total." + k)
                            for k in ("process", "loop", "other")},
        "gateway_process_cpu_pct_by_proc": share("gateway_cpu_s"),
        "engine_process_cpu_pct_by_proc": share("engine_cpu_s"),
        "client_cpu_pct": share("client_cpu_s"),
    }
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--every", type=float, default=2.0)
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    command = args.command[1:] if args.command[:1] == ["--"] else args.command
    proc = subprocess.Popen(command)
    samples: list[dict] = []
    found: dict = {}
    while proc.poll() is None:
        time.sleep(args.every)
        if not {"client", "engine", "gateway"} <= set(found):
            found = find_servers(proc.pid)
            if "benchmark/run.py" in " ".join(command):
                found.setdefault("client", proc.pid)
            continue
        got = sample(found)
        if got is not None:
            samples.append(got)
    result = {"command": command, "rc": proc.returncode,
              "table": table(samples) if len(samples) > 2 else {},
              "samples": samples}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f)
    print("way_out " + json.dumps(result["table"]), file=sys.stderr)
    return proc.returncode


if __name__ == "__main__":
    sys.exit(main())
