"""Model step: the least time one decode step of a decoder of window and
global attention layers over a mixture could take on this chip
(`models/afmoe.py`) — the weights it must read with the held experts counted
as the program's counter says they were touched, the cells the step's
attentions read by the records' own counters (`window_kv_tokens`,
`global_kv_tokens`: a window layer's stop growing at the window) and the
head, over the published bandwidth (or its operations over the published
peak, whichever is longer) — as a share of `model.decode_step_s`: the share
of the whole step."""

from benchmark import manifest, moe_counters, peaks


def read(collected: dict):
    if collected["config"].get("model_type") != "afmoe":
        return None
    step_s = manifest.load_module("layer_metrics", "model.decode_step_s").read(collected)
    recs = [r for r in moe_counters.traced(collected)
            if r["kind"] == "decode" and "window_kv_tokens" in r]
    steps = sum(moe_counters.steps_of(r, collected) for r in recs)
    if step_s is None or not steps or not collected.get("peaks"):
        return None

    def a_step(field):
        return sum(r[field] for r in recs) / steps

    w = manifest.load_module("roofline", "band_moe").decode_step(
        collected["config"], collected["engine"],
        window_cells=a_step("window_kv_tokens"),
        global_cells=a_step("global_kv_tokens"),
        rows=sum(r["tokens"] for r in recs) / steps,
        experts_touched=a_step("experts_touched"))
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], step_s,
                                             collected["peaks"])
    return share
