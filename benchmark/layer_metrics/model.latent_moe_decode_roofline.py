"""Model step: the least time one decode step of a latent-attention mixture
could take on this chip — the weights it must read with the routed experts
counted as the program's counter says they were touched, plus the latent
cache alive, over the published bandwidth (or its operations over the
published peak, whichever is longer) — as a share of `model.decode_step_s`."""

from benchmark import manifest, moe_counters, peaks, samples


def read(collected: dict):
    step_s = manifest.load_module("layer_metrics", "model.decode_step_s").read(collected)
    touched = moe_counters.touched_per_step(collected)
    if step_s is None or touched is None or not collected.get("peaks"):
        return None
    live, rows = samples.live_kv_tokens(collected, *samples.traced_interval(collected))
    w = manifest.load_module("roofline", "latent_moe").decode_step(
        collected["config"], collected["engine"], live_tokens=live, rows=rows,
        experts_touched=touched)
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], step_s,
                                             collected["peaks"])
    return share
