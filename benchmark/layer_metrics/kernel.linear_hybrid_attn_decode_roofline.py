"""Kernels: the full-attention layers' decode attention's roofline share in
a decoder that also has delta-rule layers — the live keys and values the
traced decode records counted (`global_kv_tokens`: a live row's whole length
in every full-attention layer; 30 KV heads of 128 with one query head each,
15,360 B a cell: benchmark/roofline/linear_hybrid.py) over the published
peaks, as a share of the device time the trace gives `paged_flash_decode`.
The pool stores 32 heads a cell (two dead ones, models/olmo_hybrid.py
`pool_kv_heads`); the 30 the equations need are counted, so the padding
shows as a lower share. Under a name of its own because the accepted
`kernel.paged_flash_decode_roofline` lists other cells and counts from the
client's clock."""

from benchmark import manifest, peaks, samples


def read(collected: dict):
    step = manifest.load_module("layer_metrics",
                                "kernel.delta_rule_step_roofline")
    roofline = manifest.load_module("roofline", "linear_hybrid")
    tr = collected.get("trace") or {}
    rows = samples.matching(tr.get("ops") or {}, roofline.ATTN_DECODE_OPS)
    recs = step.traced(collected)
    if not rows or not recs or not collected.get("peaks"):
        return None
    hf = collected["config"]
    seconds = sum(r["time_s"] for r in rows)
    w = roofline.attn_decode(
        hf, cells=sum(r["global_kv_tokens"] for r in recs),
        rows=sum(r["state_rows"] for r in recs)
        * roofline.layers(hf, roofline.FULL))
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], seconds,
                                             collected["peaks"])
    return share
