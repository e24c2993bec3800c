"""Reduction of a profiler trace (`.xplane.pb`, read with
`jax.profiler.ProfileData`) to what the per-layer metrics need: device busy
and idle time, time per operation and per program, and the longest idle gaps
named by what the host was doing.

Pure functions over anything shaped like ProfileData (planes → lines →
events with `name`, `start_ns`, `duration_ns`, `stats`), so the arithmetic
is checked on a hand-built stand-in in tests/benchmark/test_trace.py.
"""

from __future__ import annotations

import re
from bisect import bisect_right

DEVICE_PLANE_RE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SHAPE_RE = re.compile(r"\b(pred|[suf]\d+|bf16|f8\w*)\[([\d,]*)\]")


def device_planes(profile) -> list:
    return [p for p in profile.planes if DEVICE_PLANE_RE.match(p.name or "")]


def _line(plane, name: str):
    for ln in plane.lines:
        if ln.name == name:
            return ln
    return None


def _events(line) -> list[tuple[float, float, str]]:
    """(start_s, end_s, name) of a line's events, by start, outer first."""
    out = []
    for e in line.events:
        s = float(e.start_ns) / 1e9
        out.append((s, s + float(e.duration_ns) / 1e9, e.name))
    out.sort(key=lambda x: (x[0], -x[1]))
    return out


_INSTANCE_RE = re.compile(r"(\.(\d+|remat\d*|clone))+$")


def op_label(name: str) -> str:
    """A stable label for a device operation: its HLO name without the
    instance number, plus the type and dimensions of its (first) result. A
    TPU trace names an event by its whole HLO instruction, so
    `%fusion.412 = bf16[32,14336]{1,0:T(8,128)} fusion(...)` becomes
    `fusion_bf16_32_14336_`, and two fusions of different shapes do not
    share a row. A bare name (`paged_flash_decode.7`) loses its number."""
    result = ""
    if name.startswith("%") and " = " in name:
        name, result = name[1:].split(" = ", 1)
    base = _INSTANCE_RE.sub("", name) or name
    m = _SHAPE_RE.search(result)
    if m:
        return f"{base}_{m.group(1)}_{m.group(2).replace(',', '_')}_"
    return base


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events: list[tuple[float, float, str]]
               ) -> list[tuple[str, float]]:
    """(name, self seconds) per event of one line: an event's time
    minus the part its nested events cover (a `while` spans its body), so
    that summing over operations counts each instant once."""
    out = []
    stack: list[list] = []  # [end, name, self]

    def close_until(t: float):
        while stack and stack[-1][0] <= t:
            _end, name, self_s = stack.pop()
            out.append((name, max(0.0, self_s)))

    for s, e, name in events:
        close_until(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close_until(float("inf"))
    return out


def host_phase_at(t_wall: float, steps: list[dict]) -> str:
    """What the engine's step loop was doing at wall-clock time t, from the
    stepstats records (engine/stepstats.py): each record is stamped at its
    end and carries its phases in the order they ran."""
    order = ("plan", "draft", "host_sync", "dispatch", "compute", "fetch",
             "emit")
    ends = [r["ts"] for r in steps]
    i = bisect_right(ends, t_wall)
    if i >= len(steps):
        return "after_last_step"
    rec = steps[i]
    start = rec["ts"] - rec["total_s"]
    if t_wall < start:
        return "between_steps"
    t = start
    for ph in order:
        t += rec["phases_s"].get(ph, 0.0)
        if t_wall <= t:
            return f"{rec['kind']}.{ph}"
    return f"{rec['kind']}.emit"


def reduce(profile, *, window_s: float, steps: list[dict] | None = None,
           clock_offset_s: float | None = None, top: int = 10) -> dict:
    """The summary the launcher hands back after a traced window.

    window_s: length of the traced window on the host clock.
    steps: stepstats records (ts on the wall clock), for naming gaps.
    clock_offset_s: wall-clock seconds minus trace seconds, from a host
      annotation whose wall-clock time is known; None leaves gaps unnamed.
    """
    planes = device_planes(profile)
    if not planes:
        return {"device_planes": 0, "busy_s": None, "window_s": window_s}
    busy_per_plane = []
    ops: dict[str, dict] = {}
    modules: dict[str, dict] = {}
    gaps: list[tuple[float, float]] = []
    for pi, plane in enumerate(planes):
        line = _line(plane, OPS_LINE)
        evs = _events(line) if line is not None else []
        merged = merge([(s, e) for s, e, _ in evs])
        busy_per_plane.append(sum(e - s for s, e in merged))
        for name, self_s in self_times(evs):
            rec = ops.setdefault(op_label(name), {"time_s": 0.0, "count": 0})
            rec["time_s"] += self_s / len(planes)
            rec["count"] += 1
        mline = _line(plane, MODULES_LINE)
        if mline is not None:
            for s, e, name in _events(mline):
                key = re.sub(r"\(.*$", "", name).strip()
                rec = modules.setdefault(key, {"durations_s": []})
                rec["durations_s"].append(e - s)
        if pi == 0:
            gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    named_gaps = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        label = "unattributed"
        if steps and clock_offset_s is not None:
            label = host_phase_at((s + e) / 2 + clock_offset_s, steps)
        named_gaps.append([label, e - s])
    mod_out = {}
    for key, rec in modules.items():
        d = sorted(rec["durations_s"])
        mod_out[key] = {"count": len(d), "time_s": sum(d) / len(planes),
                        "median_s": d[len(d) // 2] if len(d) % 2
                        else (d[len(d) // 2 - 1] + d[len(d) // 2]) / 2}
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1]["time_s"])
    return {
        "device_planes": len(planes),
        "window_s": window_s,
        "busy_s": sum(busy_per_plane) / len(planes),
        "ops": {k: v for k, v in top_ops[:200]},
        "modules": mod_out,
        "breakdown": {
            "device_ops": [[k, v["time_s"]] for k, v in top_ops[:top]],
            "idle_gaps": named_gaps,
        },
    }


def find_host_event(profile, name: str) -> float | None:
    """Trace time (s) at which the first host event called `name` starts —
    the anchor that ties the trace's clock to the wall clock."""
    best = None
    for plane in profile.planes:
        if not (plane.name or "").startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name == name:
                    t = float(e.start_ns) / 1e9
                    best = t if best is None else min(best, t)
    return best


def structure(profile, per_line: int = 6) -> list[dict]:
    """Planes, lines and a few events of each with their stats: what to
    look at by hand before trusting a reduction of a new kind of trace."""
    out = []
    for plane in profile.planes:
        lines = []
        for ln in plane.lines:
            evs = []
            n = 0
            for e in ln.events:
                n += 1
                if len(evs) < per_line:
                    try:
                        stats = {k: str(v)[:120] for k, v in dict(e.stats).items()}
                    except Exception:
                        stats = {}
                    evs.append({"name": e.name, "start_ns": e.start_ns,
                                "duration_ns": e.duration_ns, "stats": stats})
            lines.append({"line": ln.name, "events": n, "first": evs})
        out.append({"plane": plane.name, "lines": lines})
    return out
