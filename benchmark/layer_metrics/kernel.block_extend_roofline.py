"""Kernels: the paged extend kernel's roofline share in block passes — what
its calls in the traced window had to read and compute
(benchmark/roofline/block_moe.py: each row's live keys and values once a
call, a block of queries a row) over the published peaks, as a share of the
device time the trace gives it. Bound by memory; the kernel's grid sweeps
the (slots x window) rectangle of pages, live or not."""

from benchmark import manifest, peaks, samples


def read(collected: dict):
    roofline = manifest.load_module("roofline", "block_moe")
    tr = collected.get("trace") or {}
    rows = samples.matching(tr.get("ops") or {}, roofline.BLOCK_EXTEND_OPS)
    if not rows or not collected.get("peaks"):
        return None
    calls = sum(r["count"] for r in rows)
    seconds = sum(r["time_s"] for r in rows)
    live, n = samples.live_kv_tokens(collected, *samples.traced_interval(collected))
    w = roofline.block_extend_call(collected["config"], live_tokens=live, rows=n)
    share, _bound = peaks.roofline_share_pct(
        w["flops"] * calls, w["bytes"] * calls, seconds, collected["peaks"])
    return share
