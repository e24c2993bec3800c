"""Model step: the least time one decode step of a decoder of window and
global attention layers over a mixture could take on this chip — the weights
it must read with the held experts counted as the program's counter says
they were touched, the global layers' live keys and values, the window
layers' live ring cells and the head, over the published bandwidth (or its
operations over the published peak, whichever is longer) — as a share of
`model.decode_step_s`: the share of the whole step."""

from benchmark import manifest, moe_counters, peaks, samples


def read(collected: dict):
    if "hybrid_layer_pattern" not in collected["config"]:
        return None
    step_s = manifest.load_module("layer_metrics", "model.decode_step_s").read(collected)
    touched = moe_counters.touched_per_step(collected)
    if step_s is None or touched is None or not collected.get("peaks"):
        return None
    live, rows = samples.live_kv_tokens(collected, *samples.traced_interval(collected))
    w = manifest.load_module("roofline", "window_moe").decode_step(
        collected["config"], collected["engine"], live_tokens=live, rows=rows,
        experts_touched=touched)
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], step_s,
                                             collected["peaks"])
    return share
