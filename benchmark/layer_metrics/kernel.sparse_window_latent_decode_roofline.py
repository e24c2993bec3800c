"""Kernels: the sliding layers' decode attention's roofline share where
three layers of 64 heads attend over a latent ring a slot
(`models/dots3_note.py`) — the latents (1,024 numbers) and shared keys of
the ring cells the traced decode records say were in use
(`window_kv_tokens`: a live row's min(len, 513) in every sliding layer),
each read once for all heads (benchmark/roofline/sparse_latent.py
`window_decode`), over the published peaks, as a share of the device time
the trace gives the kernel (`window_latent_decode`: paged_latent_decode
over a ring as a page of 640 cells, 513 of them in use)."""

from benchmark import manifest


def read(collected: dict):
    return manifest.load_module(
        "layer_metrics", "kernel.sparse_index_select_roofline").kernel_share(
        collected, "WINDOW_DECODE_OPS", "window_decode", "window_kv_tokens",
        "SLIDING")
