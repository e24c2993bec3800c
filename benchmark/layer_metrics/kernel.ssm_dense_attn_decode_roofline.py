"""Kernels: the attention layers' decode attention's roofline share in a
dense hybrid of state-space and attention layers — the live keys and values
a call reads (`global_kv_tokens`: a live row's whole length in every
attention layer; 8 KV heads of 64 with four query heads each, 2,048 B a
cell: benchmark/roofline/ssm_dense.py) over the published peaks, as a share
of the device time the trace gives `paged_flash_decode`. By the equations'
heads whatever the pool stores: a pool 64 lanes wide stored 128 wide would
read twice the bytes and show half the share. Calls and time from the same
trace rows (kernel.ssm_dense_step_roofline says why). Under a name of its
own because the accepted `kernel.paged_flash_decode_roofline` lists other
cells and counts from the client's clock."""

from benchmark import manifest, peaks


def read(collected: dict):
    step_reader = manifest.load_module("layer_metrics",
                                       "kernel.ssm_dense_step_roofline")
    roofline = manifest.load_module("roofline", step_reader.ROOFLINE)
    step = step_reader.per_step(collected, step_reader.traced(collected))
    calls, seconds = step_reader.kernel_calls(collected,
                                              roofline.ATTN_DECODE_OPS)
    if step is None or not calls or not collected.get("peaks"):
        return None
    w = roofline.attn_decode_call(
        collected["config"], cells=calls * step["live_tokens"],
        rows=calls * step["rows"])
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], seconds,
                                             collected["peaks"])
    return share
