"""Model step, a mixture held whole: of the routed experts the mixture layers
hold (64 in each of 8), the mean share one decode step touches (and so
reads), over the window's decode records. At 4 of 64 experts a token, a
choice that is even touches 87% at 32 rows (1 - (63/64)^128); every expert
is on the chip, so this is a deployment's own load and not a share of it."""

from benchmark import manifest


def read(collected: dict):
    reader = manifest.load_module("layer_metrics",
                                  "kernel.conv_moe_experts_roofline")
    step = reader.per_step(collected, reader.counted(collected))
    if step is None:
        return None
    slots = manifest.load_module("roofline", reader.ROOFLINE).expert_slots(
        collected["config"])
    return 100.0 * step["experts_touched"] / slots
