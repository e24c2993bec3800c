"""Model step: the least time one decode step of a dense hybrid of
state-space and attention layers could take on this chip — every weight read
once (the embedding table among them: it is the head), the recurrent state
read and written for the rows the traced decode records say were advanced,
the convolution's rows with it, and the keys and values they say were alive,
over the published bandwidth (or its operations over the published peak,
whichever is longer: benchmark/roofline/ssm_dense.py `decode_step`) — as a
share of `model.decode_step_s`: the share of the whole step."""

from benchmark import manifest, peaks


def read(collected: dict):
    step_s = manifest.load_module("layer_metrics", "model.decode_step_s").read(collected)
    step_reader = manifest.load_module("layer_metrics",
                                       "kernel.ssm_dense_step_roofline")
    step = step_reader.per_step(collected, step_reader.traced(collected))
    if step_s is None or step is None or not collected.get("peaks"):
        return None
    w = manifest.load_module("roofline", step_reader.ROOFLINE).decode_step(
        collected["config"], collected["engine"], **step)
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], step_s,
                                             collected["peaks"])
    return share
