"""Kernels: the full layers' decode attention's roofline share where a query
attends over the 2,048 cells its indexer picked (`models/dots3_note.py`) —
the latents and shared keys of the cells the traced decode records say were
CHOSEN (`index_selected_cells`: a live row's min(len, 2,048) in every full
layer), each read once for all 128 heads (benchmark/roofline/sparse_latent.py
`sparse_decode`), over the published peaks, as a share of the device time
the trace gives the kernel (`sparse_latent_decode`). The kernel reads every
live page under the selection's mask, so at contexts of 12-17k it reads 6-8
times the cells the account counts: the share says how far a kernel that
read the chosen cells alone could go."""

from benchmark import manifest


def read(collected: dict):
    return manifest.load_module(
        "layer_metrics", "kernel.sparse_index_select_roofline").kernel_share(
        collected, "SPARSE_DECODE_OPS", "sparse_decode",
        "index_selected_cells", "FULL")
