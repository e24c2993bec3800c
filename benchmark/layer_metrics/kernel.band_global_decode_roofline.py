"""Kernels: the global layers' decode attention's roofline share in a
decoder whose other attention layers read a window — the live keys and
values the traced decode records counted (`global_kv_tokens`: a row's whole
length in every global layer; 128 wide on 4 KV heads) over the published
peaks, as a share of the device time the trace gives `paged_flash_decode`.
`kernel.band_window_decode_roofline`'s reading with the other counter and
kernel; under a name of its own because the accepted
`kernel.paged_flash_decode_roofline` lists other cells and counts by the
clients' records."""

from benchmark import manifest


def read(collected: dict):
    return manifest.load_module(
        "layer_metrics", "kernel.band_window_decode_roofline").attention_share(
            collected, "global_kv_tokens", "GLOBAL_DECODE_OPS")
