"""Kernels: the window layers' decode attention's roofline share — the ring
cells the traced decode records say the live rows held (`window_kv_tokens`:
a row's min(len, window) in every window layer; keys 192 and values 128 wide
on 8 KV heads: benchmark/roofline/window_moe.py) over the published peaks,
as a share of the device time the trace gives `paged_window_decode`. Bound
by memory: a step reads every live cell of a ring once, whatever the
context."""

from benchmark import manifest, moe_counters, peaks, samples

def read(collected: dict):
    return attention_share(collected, "window_kv_tokens", "WINDOW_DECODE_OPS",
                           "window_decode")


def attention_share(collected: dict, counter: str, ops: str, account: str):
    """`counter`, `ops`, `account`: the step records' field, and the names
    of the kernel's trace rows and of its account in roofline/window_moe.py
    (kernel.window_global_decode_roofline reads the other three)."""
    hf = collected["config"]
    if "hybrid_layer_pattern" not in hf:
        return None
    roofline = manifest.load_module("roofline", "window_moe")
    tr = collected.get("trace") or {}
    rows = samples.matching(tr.get("ops") or {}, getattr(roofline, ops))
    recs = [r for r in moe_counters.traced(collected)
            if r["kind"] == "decode" and counter in r]
    if not rows or not recs or not collected.get("peaks"):
        return None
    seconds = sum(r["time_s"] for r in rows)
    w = getattr(roofline, account)(hf, cells=sum(r[counter] for r in recs))
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], seconds,
                                             collected["peaks"])
    return share
