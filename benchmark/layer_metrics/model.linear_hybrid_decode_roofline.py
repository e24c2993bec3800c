"""Model step: the least time one decode step of a hybrid of delta-rule and
full-attention layers could take on this chip — the weights it must read,
the rule's state read and written for the rows the traced decode records
say were advanced, and the keys and values they say were alive, over the
published bandwidth (or its operations over the published peak, whichever
is longer) — as a share of `model.decode_step_s`: the share of the whole
step."""

from benchmark import manifest, peaks


def read(collected: dict):
    step_s = manifest.load_module("layer_metrics", "model.decode_step_s").read(collected)
    step = manifest.load_module("layer_metrics",
                                "kernel.delta_rule_step_roofline")
    recs = step.traced(collected)
    if step_s is None or not recs or not collected.get("peaks"):
        return None
    w = step.step_account(collected, recs)
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], step_s,
                                             collected["peaks"])
    return share
