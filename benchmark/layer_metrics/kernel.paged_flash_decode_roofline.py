"""Kernels: the `paged_flash_decode` kernel's roofline share — what its calls
in the traced window had to read and compute (benchmark/roofline/) over the
published peaks, as a share of the device time the trace gives it. Bound by
memory: a decode step reads every live key and value once."""

from benchmark import manifest, peaks, samples


def read(collected: dict):
    tr = collected.get("trace") or {}
    rows = samples.matching(tr.get("ops") or {},
                            collected["settings"]["kernels"]["paged_flash_decode"])
    if not rows or not collected.get("peaks"):
        return None
    calls = sum(r["count"] for r in rows)
    seconds = sum(r["time_s"] for r in rows)
    live, n = samples.live_kv_tokens(collected, *samples.traced_interval(collected))
    w = manifest.load_module("roofline", "paged_flash_decode").work(
        collected["config"], collected["engine"], live_tokens=live, rows=n)
    share, _bound = peaks.roofline_share_pct(
        w["flops"] * calls, w["bytes"] * calls, seconds, collected["peaks"])
    return share
