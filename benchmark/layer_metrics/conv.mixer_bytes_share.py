"""Model step, the gated short convolutions: the conv mixers' part — their
weights (a norm, [2048, 6144], three taps a channel, [2048, 2048] a layer)
and the two carried rows read and written for the (row, layer) pairs the
window's decode records say were moved (`conv_rows`) — of the bytes one
decode step must move (benchmark/roofline/conv_moe.py `decode_step`, the
experts as touched and the context as alive by the same records). It says
how little of a step the mixer that makes this model different is, and grows
only if the experts' read shrinks."""

from benchmark import manifest


def read(collected: dict):
    reader = manifest.load_module("layer_metrics",
                                  "kernel.conv_moe_experts_roofline")
    step = reader.per_step(collected, reader.counted(collected))
    if step is None:
        return None
    w = manifest.load_module("roofline", reader.ROOFLINE).decode_step(
        collected["config"], collected["engine"], **step)
    return 100.0 * w["conv_bytes"] / w["bytes"]
