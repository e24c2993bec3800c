"""Kernels: the state step's roofline share in a dense hybrid of state-space
and attention layers (`models/granite_hybrid.py`: ONE group of B and C, 36
such layers a step) — each (row, layer)'s recurrent state read and written,
its inputs beside it (benchmark/roofline/ssm_dense.py), over the published
peaks, as a share of the device time the trace gives `ssm_decode_step`. The
kernel walks every slot, so rows that do not decode cost it time and count
for nothing here.

THE JOIN. The accepted `kernel.ssm_decode_step_roofline` sums `state_rows`
over the step records whose middle lies between the trace's wall-clock
start and stop, and divides by ALL the device time the trace holds for the
kernel; where the two cover different stretches of the window (the profiler
starts recording before `wall_start` is stamped and may stop holding events
before `wall_stop`) the share is off by their ratio, to either side
(PERF.md section 7). The readers here take the CALLS from the same trace
rows as the time (`count`), and from the records only what a call moves on
average (`per_step`): a trace that holds more or fewer steps than the
records cannot move the share."""

from benchmark import manifest, peaks, samples

ROOFLINE = "ssm_dense"


def counted(collected: dict) -> list[dict]:
    """The window's decode records of a dense state-space hybrid: those
    that carry both of its counters. Nothing for any other configuration (a
    delta-rule hybrid counts the same two) and nothing for a program that
    serves no such fields."""
    if "mamba_n_heads" not in collected["config"]:
        return []
    return [r for r in collected.get("steps") or []
            if r["kind"] == "decode" and "state_rows" in r
            and "global_kv_tokens" in r]


def traced(collected: dict) -> list[dict]:
    """Those of `counted` whose middle lies in the traced part of the window
    (the trace's wall-clock start and stop)."""
    tr = collected.get("trace") or {}
    if "wall_start" not in tr or "wall_stop" not in tr:
        return []
    return [r for r in counted(collected)
            if tr["wall_start"] <= r["ts"] - r["total_s"] / 2 <= tr["wall_stop"]]


def per_step(collected: dict, recs: list[dict]) -> dict | None:
    """What ONE decode step of `recs` moved, as the program counted it:
    `rows` advanced (in every state-space layer) and `live_tokens` of
    context alive (read by every attention layer)."""
    roofline = manifest.load_module("roofline", ROOFLINE)
    steps = sum(max(1, r["tokens"] // max(1, r["active_slots"]))
                for r in recs)  # a burst's record stands for its k steps
    if not steps:
        return None
    n_a = roofline.layers(collected["config"], roofline.ATTENTION)
    return {"rows": sum(r["state_rows"] for r in recs) / steps,
            "live_tokens": sum(r["global_kv_tokens"] for r in recs)
            / (n_a * steps)}


def kernel_calls(collected: dict, ops: list[str]):
    """(calls, device seconds) of a kernel in the trace: both from the SAME
    rows of its table."""
    tr = collected.get("trace") or {}
    rows = samples.matching(tr.get("ops") or {}, ops)
    planes = max(1, tr.get("device_planes") or 1)
    return (sum(r["count"] for r in rows) / planes,
            sum(r["time_s"] for r in rows))


def read(collected: dict):
    roofline = manifest.load_module("roofline", ROOFLINE)
    step = per_step(collected, traced(collected))
    calls, seconds = kernel_calls(collected, roofline.SSM_STEP_OPS)
    if step is None or not calls or not collected.get("peaks"):
        return None
    w = roofline.ssm_step_call(collected["config"], rows=calls * step["rows"])
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], seconds,
                                             collected["peaks"])
    return share
