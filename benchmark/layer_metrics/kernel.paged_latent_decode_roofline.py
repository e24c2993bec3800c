"""Kernels: the `paged_latent_decode` kernel's roofline share — what its
calls in the traced window had to read and compute (benchmark/roofline/
latent_moe.py) over the published peaks, as a share of the device time the
trace gives it. A page of latents is read once for all 32 heads."""

from benchmark import manifest, peaks, samples


def read(collected: dict):
    roofline = manifest.load_module("roofline", "latent_moe")
    tr = collected.get("trace") or {}
    rows = samples.matching(tr.get("ops") or {}, roofline.LATENT_DECODE_OPS)
    if not rows or not collected.get("peaks"):
        return None
    calls = sum(r["count"] for r in rows)
    seconds = sum(r["time_s"] for r in rows)
    live, n = samples.live_kv_tokens(collected, *samples.traced_interval(collected))
    w = roofline.latent_decode_call(collected["config"], live_tokens=live, rows=n)
    share, _bound = peaks.roofline_share_pct(
        w["flops"] * calls, w["bytes"] * calls, seconds, collected["peaks"])
    return share
