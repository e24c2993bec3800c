"""Model step: the least time one decode step of a gated-short-convolution
mixture could take on this chip (`models/lfm2_moe.py`) — every weight
outside the experts read once (the embedding table among them: it is the
head), the experts counted as the traced decode records say they were
TOUCHED and not as held, the carried rows read and written for the (row,
layer) pairs they say were moved, and the keys and values they say were
alive, over the published bandwidth (or its operations over the published
peak, whichever is longer: benchmark/roofline/conv_moe.py `decode_step`) —
as a share of `model.decode_step_s`: the share of the whole step."""

from benchmark import manifest, peaks


def read(collected: dict):
    step_s = manifest.load_module("layer_metrics", "model.decode_step_s").read(collected)
    reader = manifest.load_module("layer_metrics",
                                  "kernel.conv_moe_experts_roofline")
    step = reader.per_step(collected, reader.traced(collected))
    if step_s is None or step is None or not collected.get("peaks"):
        return None
    w = manifest.load_module("roofline", reader.ROOFLINE).decode_step(
        collected["config"], collected["engine"], **step)
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], step_s,
                                             collected["peaks"])
    return share
