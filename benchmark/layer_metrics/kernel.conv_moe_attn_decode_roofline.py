"""Kernels: the attention layers' decode attention's roofline share in a
gated-short-convolution mixture (`models/lfm2_moe.py`: two attention layers
of ten) — the live keys and values a call reads (`global_kv_tokens`: a live
row's whole length in every attention layer; 8 KV heads of 64 with four
query heads each, 2,048 B a cell: benchmark/roofline/conv_moe.py) over the
published peaks, as a share of the device time the trace gives
`paged_flash_decode`. By the equations' heads whatever the pool stores: a
pool 64 lanes wide stored 128 wide would read twice the bytes and show half
the share. Calls and time from the same trace rows
(kernel.conv_moe_experts_roofline says why)."""

from benchmark import manifest, peaks


def read(collected: dict):
    reader = manifest.load_module("layer_metrics",
                                  "kernel.conv_moe_experts_roofline")
    roofline = manifest.load_module("roofline", reader.ROOFLINE)
    step = reader.per_step(collected, reader.traced(collected))
    calls, seconds = reader.kernel_calls(collected, roofline.ATTN_DECODE_OPS)
    if step is None or not calls or not collected.get("peaks"):
        return None
    a_layer = roofline.layers(collected["config"], roofline.ATTENTION)
    w = roofline.attn_decode_call(
        collected["config"], cells=calls * step["live_cells"] / a_layer,
        rows=calls * step["rows"])
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], seconds,
                                             collected["peaks"])
    return share
