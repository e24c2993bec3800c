"""Kernels: the window layers' decode attention's roofline share where the
window is a band of pages a slot (`models/afmoe.py`) — the cells the traced
decode records say the live rows' windows held (`window_kv_tokens`: a row's
min(len, window) in every window layer; keys and values 128 wide on 4 KV
heads: benchmark/roofline/band_moe.py) over the published peaks, as a share
of the device time the trace gives `paged_band_decode`. Bound by memory: a
step reads every cell of a row's window once; the cells of the oldest and
newest page that the mask drops are read and are not work."""

from benchmark import manifest, moe_counters, peaks, samples


def read(collected: dict):
    return attention_share(collected, "window_kv_tokens", "WINDOW_DECODE_OPS")


def attention_share(collected: dict, counter: str, ops: str):
    """`counter`, `ops`: the step records' field and the name of the
    kernel's trace rows in roofline/band_moe.py
    (kernel.band_global_decode_roofline reads the other two)."""
    hf = collected["config"]
    if hf.get("model_type") != "afmoe":
        return None
    roofline = manifest.load_module("roofline", "band_moe")
    tr = collected.get("trace") or {}
    rows = samples.matching(tr.get("ops") or {}, getattr(roofline, ops))
    recs = [r for r in moe_counters.traced(collected)
            if r["kind"] == "decode" and counter in r]
    if not rows or not recs or not collected.get("peaks"):
        return None
    seconds = sum(r["time_s"] for r in rows)
    w = roofline.attention_decode(hf, cells=sum(r[counter] for r in recs))
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], seconds,
                                             collected["peaks"])
    return share
