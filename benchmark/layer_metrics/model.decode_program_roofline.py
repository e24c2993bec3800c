"""Model step: the least time one decode step could take on this chip (the
bytes it must read — weights and live KV — over the published bandwidth, or
its operations over the published peak, whichever is longer) as a share of
`model.decode_step_s`. Bound by memory at these batch sizes."""

from benchmark import manifest, peaks, samples


def read(collected: dict):
    step_s = manifest.load_module("layer_metrics", "model.decode_step_s").read(collected)
    if step_s is None or not collected.get("peaks"):
        return None
    live, rows = samples.live_kv_tokens(collected, *samples.traced_interval(collected))
    w = manifest.load_module("roofline", "decode_program").work(
        collected["config"], collected["engine"], live_tokens=live, rows=rows)
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], step_s,
                                             collected["peaks"])
    return share
