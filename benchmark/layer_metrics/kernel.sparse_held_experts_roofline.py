"""Kernels: the grouped expert products' roofline share where a chip holds
32 of 256 three-matrix experts of 5120 x 1536 under sparse and sliding
latent attention (`models/dots3_note.py`) — the three matrices of the held
experts the program's counter says were touched and the operations of the
assignments they took (another chip's assignment is not computed here; the
shared expert is a plain product and not in these kernels), by the step
records of the traced part of the window (prefills among them: their
products run under the same names), over the device time the trace gives
the grouped-matmul kernels. Bound by memory in decode, by the MXU in an
extend chunk of 512 tokens."""

from benchmark import manifest, moe_counters, peaks, samples


def read(collected: dict):
    roofline = manifest.load_module("roofline", "sparse_latent")
    if not roofline.is_sparse(collected["config"]):
        return None
    tr = collected.get("trace") or {}
    rows = samples.matching(tr.get("ops") or {}, roofline.ROUTED_EXPERT_OPS)
    recs = moe_counters.traced(collected)
    if not rows or not recs or not collected.get("peaks"):
        return None
    w = roofline.held_experts(
        collected["config"],
        experts_touched=sum(r["experts_touched"] for r in recs),
        assignments=sum(r["expert_assignments"] for r in recs))
    share, _bound = peaks.roofline_share_pct(
        w["flops"], w["bytes"], sum(r["time_s"] for r in rows),
        collected["peaks"])
    return share
