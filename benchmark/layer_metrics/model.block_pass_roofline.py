"""Model step, a block family's: the least time one BLOCK PASS could take on
this chip — the weights it must read with the routed experts counted as the
program's counter says they were touched, plus the keys and values alive,
over the published bandwidth (or its operations over the published peak,
whichever is longer) — as a share of `model.decode_step_s`, which for such
a family is the median device time of one pass of the burst."""

from benchmark import manifest, peaks, samples


def read(collected: dict):
    step_s = manifest.load_module("layer_metrics", "model.decode_step_s").read(collected)
    recs = [r for r in collected.get("steps") or []
            if r.get("kind") == "decode" and r.get("block_passes")
            and "experts_touched" in r]
    passes = sum(r["block_passes"] for r in recs)
    if step_s is None or not passes or not collected.get("peaks"):
        return None
    live, rows = samples.live_kv_tokens(collected, *samples.traced_interval(collected))
    w = manifest.load_module("roofline", "block_moe").block_pass(
        collected["config"], collected["engine"], live_tokens=live, rows=rows,
        experts_touched=sum(r["experts_touched"] for r in recs) / passes)
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], step_s,
                                             collected["peaks"])
    return share
