"""Model step, state-space layers: the recurrent state's part (read and
written for the rows the decode records say were advanced, `state_rows`) of
the bytes one decode step must move (benchmark/roofline/hybrid_moe.py
`decode_step`, at the experts the counter says were touched and the context
alive over the window). It grows with the rows and not with the context."""

from benchmark import manifest, moe_counters, samples


def read(collected: dict):
    recs = [r for r in moe_counters.counted(collected) if "state_rows" in r]
    steps = sum(moe_counters.steps_of(r, collected) for r in recs)
    touched = moe_counters.touched_per_step(collected)
    if not steps or touched is None:
        return None
    live, _rows = samples.live_kv_tokens(collected, 0.0,
                                         float(collected["seconds"]))
    w = manifest.load_module("roofline", "hybrid_moe").decode_step(
        collected["config"], collected["engine"], live_tokens=live,
        rows=sum(r["state_rows"] for r in recs) / steps,
        experts_touched=touched)
    return 100.0 * w["state_bytes"] / w["bytes"]
