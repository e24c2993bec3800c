"""Kernels: the grouped expert products' roofline share in block passes —
the weights of the experts the program's counter says were touched and the
operations of the assignments made, by the step records of the traced part
of the window, over the device time the trace gives the grouped-matmul
kernels. 128 rows a pass x 8 choices touch nearly every expert of a layer:
bound by memory, an expert's three matrices for 8 rows."""

from benchmark import manifest, moe_counters, peaks, samples


def read(collected: dict):
    roofline = manifest.load_module("roofline", "block_moe")
    tr = collected.get("trace") or {}
    rows = samples.matching(tr.get("ops") or {}, roofline.ROUTED_EXPERT_OPS)
    recs = moe_counters.traced(collected)
    if not rows or not recs or not collected.get("peaks"):
        return None
    seconds = sum(r["time_s"] for r in rows)
    w = roofline.routed_experts(
        collected["config"],
        experts_touched=sum(r["experts_touched"] for r in recs),
        assignments=sum(r["expert_assignments"] for r in recs))
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], seconds,
                                             collected["peaks"])
    return share
