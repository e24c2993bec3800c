"""Kernels: the state step's roofline share — the recurrent state of the
rows the traced decode records say were advanced (`state_rows`, a layer's
state read and written for each, its inputs beside it:
benchmark/roofline/hybrid_moe.py) over the published peaks, as a share of
the device time the trace gives `ssm_decode_step`. The kernel walks every
slot, so rows that do not decode cost it time and count for nothing here."""

from benchmark import manifest, moe_counters, peaks, samples


def read(collected: dict):
    hf = collected["config"]
    if "hybrid_override_pattern" not in hf:
        return None
    roofline = manifest.load_module("roofline", "hybrid_moe")
    tr = collected.get("trace") or {}
    rows = samples.matching(tr.get("ops") or {}, roofline.SSM_STEP_OPS)
    recs = [r for r in moe_counters.traced(collected)
            if r["kind"] == "decode" and "state_rows" in r]
    if not rows or not recs or not collected.get("peaks"):
        return None
    seconds = sum(r["time_s"] for r in rows)
    w = roofline.ssm_step_call(hf, rows=sum(r["state_rows"] for r in recs)
                               * roofline.layers(hf, "M"))
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], seconds,
                                             collected["peaks"])
    return share
